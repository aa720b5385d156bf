import os
# compile-only on forced host devices: never claim an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Memory-lower-bound pass: re-measure cost with ONLY layer scans unrolled.

Inner scans (attention kv tiles, GLA chunks, loss chunks) stay rolled, so
`bytes accessed` counts their bodies once -> a LOWER bound on per-device
HBM traffic that avoids the fusion-subsumed-slice inflation of the full
unroll (see models/scanning.py). Results are merged into the existing
results/dryrun/*.json as the "cost_lb" field.

  PYTHONPATH=src python -m repro.launch.bytes_pass [--out results/dryrun]
"""

import argparse
import dataclasses
import json
from pathlib import Path

from repro.configs import ARCHS, get_config
from repro.launch.dryrun import _cost_vector, build_lowered
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES
from repro.models.scanning import set_unroll
from repro.sharding.rules import ShardingRules, use_rules


def cell_lb(arch, shape, mesh, rules):
    cfg = get_config(arch)
    period = len(cfg.layer_pattern)
    full_p, tail = cfg.pattern_groups()
    set_unroll("layers")
    try:
        def measure(n_layers):
            cfg_v = dataclasses.replace(cfg, num_layers=n_layers)
            return _cost_vector(
                build_lowered(arch, shape, mesh, rules, cfg=cfg_v).compile())

        c1 = measure(period)
        c2 = measure(2 * period)
        ct = measure(period + tail) if tail else None
    finally:
        set_unroll("none")
    out = {}
    for k in c1:
        total = c1[k] + (full_p - 1) * (c2[k] - c1[k])
        if ct is not None:
            total += ct[k] - c1[k]
        out[k] = total
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args(argv)
    out_dir = Path(args.out)
    mesh = make_production_mesh()
    rules = ShardingRules.default()
    for arch in ARCHS:
        for shape in SHAPES:
            p = out_dir / f"{arch}__{shape}__single_pod.json"
            if not p.exists():
                continue
            rec = json.loads(p.read_text())
            if rec.get("skipped") or not rec.get("ok") or "cost_lb" in rec:
                continue
            try:
                with mesh, use_rules(rules):
                    rec["cost_lb"] = cell_lb(arch, shape, mesh, rules)
                print(f"[ok] {arch} {shape}", flush=True)
            except Exception as e:
                rec["cost_lb_error"] = f"{type(e).__name__}: {e}"
                print(f"[fail] {arch} {shape}: {e}", flush=True)
            p.write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
