"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name (``bench/discover.py``). Set-up (imports, device start, inputs,
compilation, warm-up) runs first and is reported as ``setup_s``; the
traffic's generator then measures for ``--seconds`` and checks what the
window produced against the configuration's plain reference.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared beside its limit, also printed as the
last lines of stderr. An earlier line counts the compilations inside the
window. Without a TPU, or with fewer chips than the cell asks for, the
run prints no result and exits with 3.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, discover, harness  # noqa: E402

NO_CHIP = 3


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, *, config=None, traffic=None, t_start=T_START,
             **generator_kw) -> tuple[dict, list]:
    """Run cell ``name``; return (result line, checks).

    ``config``/``traffic`` replace the files' dicts (tests run the same
    path at a small size); ``generator_kw`` goes to the generator's ``run``.
    """
    import jax
    spec = discover.find_cell(bench, name)
    config = config or discover.load_config(spec["config"])
    traffic = traffic or discover.load_traffic(spec["traffic"])
    generator = discover.load_generator(traffic["generator"])
    counter = harness.CompileCounter()
    tmp = Path(tempfile.mkdtemp(prefix="bench_"))
    try:
        cell = harness.Cell(
            name=name, config=config, traffic=traffic, seed=seed,
            seconds=seconds, trace=trace, chips=spec["chips"],
            reference=discover.load_reference(spec["config"]), tmp=tmp)
        out = generator.run(cell, counter, **generator_kw)
        print(json.dumps({"compiles_in_window": out.compiles_in_window,
                          "layer": out.layer}, default=str), flush=True)
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": jax.device_count(),
                  "memory_peak_bytes": out.memory_peak_bytes}
        line = {"correct": (check.verdict(out.checks) and out.failed == 0),
                "attempted": out.attempted, "failed": out.failed}
        if trace:
            from bench import xplane
            ctx = xplane.context(out, dev.device_kind)
            metrics = {}
            for m in discover.per_layer_for(bench, name):
                value = discover.load_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
            line.update(metrics=metrics, device=device,
                        breakdown=ctx.breakdown())
        else:
            values = dict(out.metrics,
                          setup_s=out.window_start - t_start)
            line.update(metrics={m["name"]: {"value": values[m["name"]],
                                             "unit": m["unit"]}
                                 for m in discover.end_to_end_for(bench,
                                                                  name)},
                        device=device)
        line["checks"] = check.report(out.checks)
        return line, out.checks
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = discover.load_benchmark()
    chips = discover.find_cell(bench, args.workload)["chips"]
    # a fixed path inside the checkout: the path is part of the cache key
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    # the TPU runtime would otherwise log to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return NO_CHIP
    line, checks = run_cell(bench, args.workload, args.seed, args.seconds,
                            bool(args.trace))
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
