"""Readings that a cell's check limit is set from, many seeds in one
process: the program's number (a sound run with a short window) and the
control's (the reference in the precision below the configuration's, in
the program's place, at the cell's own size).

    python3 bench/readings.py --workload <name> --seeds 11,12,13 \
        [--seconds 1] [--program 1] [--control 1]

Prints one JSON line per seed and kind: ``{"kind", "seed", "value",
"correct"}``; the control's ``correct`` is the verdict of the same
``check.verdict`` a run's checks go through, at the configuration's
limit, and has to be false.
Not run by the benchmark itself; its numbers and the limits set from
them are in PERF.md. A cell staged in ``bench/staged/``, where there is
one, is found too (``discover.with_staged``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, discover, harness, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    # the TPU runtime would otherwise log to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = discover.with_staged(discover.load_benchmark())
    spec = discover.find_cell(bench, args.workload)
    if jax.devices()[0].platform != "tpu" or (
            len(jax.devices()) < spec["chips"]):
        print("readings: needs the cell's TPU chips", file=sys.stderr)
        return run.NO_CHIP
    config = discover.load_config(spec["config"])
    traffic = discover.load_traffic(spec["traffic"])
    generator = discover.load_generator(traffic["generator"])
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.program:
            t0 = time.monotonic()
            line, checks = run.run_cell(bench, args.workload, seed,
                                        args.seconds, False, t_start=t0)
            print(json.dumps({"kind": "program", "seed": seed,
                              "value": checks[0][1],
                              "correct": line["correct"],
                              "metrics": line["metrics"]}), flush=True)
        if args.control:
            with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
                cell = harness.Cell(
                    name=args.workload, config=config, traffic=traffic,
                    seed=seed, seconds=args.seconds, trace=False,
                    chips=spec["chips"],
                    reference=discover.load_reference(spec["config"]),
                    tmp=Path(tmp))
                t0 = time.monotonic()
                checks = generator.control(cell)
            print(json.dumps({"kind": "control", "seed": seed,
                              "value": checks[0][1],
                              "correct": check.verdict(checks),
                              "checks": check.report(checks),
                              "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
