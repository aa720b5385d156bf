import os
# compile-only on forced host devices: never claim an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(*abstract_inputs).compile()`` must succeed
on the 16x16 single-pod mesh AND the (2,16,16) multi-pod mesh for every
assigned cell, and the compiled artifact yields the roofline inputs:
``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()`` (FLOPs /
bytes), and the optimized HLO (collective bytes).

NOTE the first two lines: XLA locks the device count at first backend init,
so the 512-device override must precede every other import. Tests and
benches never import this module (they see 1 device).

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
      --mesh single_pod [--out out.json] [--rules k=v ...]
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.launch.hlo_analysis import collective_stats, cost_analysis_dict
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import SHAPES, cell_runnable, input_specs
from repro.models.scanning import set_unroll
from repro.models.transformer import TransformerLM
from repro.sharding.rules import (ShardingRules, abstract_params,
                                  param_shardings, resolve_pspec,
                                  tree_shardings, use_rules)
from repro.train.trainer import TrainerConfig, make_train_step, state_shardings

BATCH_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "frames": ("batch", None, None),
    "patches": ("batch", None, None),
}


def _batch_shardings(batch_abs, rules, mesh):
    return {
        k: NamedSharding(mesh, resolve_pspec(tuple(v.shape), BATCH_AXES[k],
                                             rules, mesh))
        for k, v in batch_abs.items()
    }


def pick_optimizer(cfg) -> str:
    """Adafactor for >20B-param configs (halves optimizer HBM), else adamw."""
    return "adafactor" if cfg.n_params() > 20e9 else "adamw"


CFG_OVERRIDES: dict = {}
GRAD_ACCUM = [1]


def _apply_cfg_overrides(cfg):
    if CFG_OVERRIDES:
        cfg = dataclasses.replace(cfg, **CFG_OVERRIDES)
    return cfg


def build_lowered(arch: str, shape: str, mesh, rules: ShardingRules,
                  optimizer: str | None = None, cfg=None):
    cfg = _apply_cfg_overrides(cfg or get_config(arch))
    case = SHAPES[shape]
    if case.mode == "prefill":
        # prefill has no backward: larger tiles bound the Python-unrolled
        # q-chunk count at 32k (HLO size) without a remat-memory cost
        cfg = dataclasses.replace(cfg, attn_q_chunk=4096, attn_kv_chunk=2048)
    model = TransformerLM(cfg)
    batch_abs = input_specs(cfg, shape)

    if case.mode == "train":
        specs = model.param_specs()
        params_abs = abstract_params(specs)
        accum = GRAD_ACCUM[0]
        tc = TrainerConfig(optimizer=optimizer or pick_optimizer(cfg),
                           grad_accum=accum)
        if accum > 1:  # microbatched inputs: (accum, B/accum, ...)
            batch_abs = {k: jax.ShapeDtypeStruct(
                (accum, v.shape[0] // accum) + v.shape[1:], v.dtype)
                for k, v in batch_abs.items()}
        opt, step_fn = make_train_step(model, tc)
        state_abs = {
            "params": params_abs,
            "opt_state": jax.eval_shape(opt.init, params_abs),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        state_sh = state_shardings(model, state_abs, rules, mesh)
        if GRAD_ACCUM[0] > 1:
            batch_sh = {k: NamedSharding(mesh, resolve_pspec(
                tuple(v.shape), (None,) + BATCH_AXES[k], rules, mesh))
                for k, v in batch_abs.items()}
        else:
            batch_sh = _batch_shardings(batch_abs, rules, mesh)
        rep = NamedSharding(mesh, P())
        metrics_sh = {"loss": rep, "grad_norm": rep, "lr": rep}
        fn = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, metrics_sh), donate_argnums=0)
        return fn.lower(state_abs, batch_abs)

    params_abs = abstract_params(model.param_specs(), dtype="bfloat16")
    params_sh = param_shardings(model.param_specs(), rules, mesh)

    if case.mode == "prefill":
        batch_sh = _batch_shardings(batch_abs, rules, mesh)
        fn = jax.jit(lambda p, b: model.prefill(p, b),
                     in_shardings=(params_sh, batch_sh))
        return fn.lower(params_abs, batch_abs)

    # decode
    caches_abs, token_abs, pos_abs = batch_abs
    cache_sh = tree_shardings(caches_abs, model.cache_axes(), rules, mesh)
    tok_sh = NamedSharding(mesh, resolve_pspec(
        tuple(token_abs.shape), ("cache_batch", None), rules, mesh))
    pos_sh = NamedSharding(mesh, P())
    fn = jax.jit(model.decode_step,
                 in_shardings=(params_sh, cache_sh, tok_sh, pos_sh),
                 donate_argnums=1)
    return fn.lower(params_abs, caches_abs, token_abs, pos_abs)


def _cost_vector(compiled) -> dict:
    cost = cost_analysis_dict(compiled.cost_analysis())
    colls = collective_stats(compiled.as_text())
    vec = {
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "transcendentals": cost.get("transcendentals", 0.0),
        "collective_bytes": colls["total_bytes"],
    }
    for k in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute"):
        vec[f"cb_{k}"] = colls[k]["bytes"]
        vec[f"cn_{k}"] = colls[k]["count"]
    return vec


def _extrapolated_cost(arch, shape, mesh, rules, optimizer) -> dict:
    """Cost pass: XLA's cost analysis counts while-loop bodies once (see
    models/scanning.py), so costs are measured on FULLY UNROLLED reduced-
    depth variants and extrapolated linearly in the period count:

        total = C(1p) + (periods-1) * (C(2p) - C(1p)) + [C(1p+tail) - C(1p)]

    which is exact for layer-uniform cost (the stack is periodic by
    construction). Validated against a full unroll in tests/test_roofline.py.
    """
    cfg = get_config(arch)
    period = len(cfg.layer_pattern)
    full_p, tail = cfg.pattern_groups()
    # SSM/hybrid patterns at long seq: a full unroll of the GLA chunk scans
    # (256-2048 iterations x depth) blows up compile time; fall back to
    # layers-only unroll there (flops are then a LOWER bound for the
    # inter-chunk scan portion — recorded as cost.mode).
    heavy_inner = (any(k in cfg.layer_pattern for k in "MR")
                   and SHAPES[shape].mode in ("train", "prefill"))
    mode = "layers" if heavy_inner else "all"
    set_unroll(mode)
    try:
        def measure(n_layers):
            cfg_v = dataclasses.replace(_apply_cfg_overrides(cfg),
                                        num_layers=n_layers)
            lowered = build_lowered(arch, shape, mesh, rules, optimizer,
                                    cfg=cfg_v)
            return _cost_vector(lowered.compile())

        c1 = measure(period)
        c2 = measure(2 * period)
        ct = measure(period + tail) if tail else None
    finally:
        set_unroll(False)

    out = {}
    for k in c1:
        per = c2[k] - c1[k]
        total = c1[k] + (full_p - 1) * per
        if ct is not None:
            total += ct[k] - c1[k]
        out[k] = total
    out["_per_period"] = {k: c2[k] - c1[k] for k in c1}
    out["_fixed"] = {k: 2 * c1[k] - c2[k] for k in c1}
    out["mode"] = mode
    return out


def run_cell(arch: str, shape: str, mesh_name: str, rules_overrides=None,
             optimizer: str | None = None, keep_hlo: bool = False,
             skip_cost: bool = False) -> dict:
    multi_pod = mesh_name == "multi_pod"
    cfg = get_config(arch)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "mode": SHAPES[shape].mode, "ok": False}
    runnable, reason = cell_runnable(cfg, shape)
    if not runnable:
        rec.update(skipped=True, reason=reason, ok=True)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = ShardingRules.default(multi_pod=multi_pod)
    if rules_overrides:
        rules = rules.with_overrides(**rules_overrides)

    try:
        t0 = time.monotonic()
        with mesh, use_rules(rules):
            # ---- pass 1: production (scanned) form — compile proof + memory
            lowered = build_lowered(arch, shape, mesh, rules, optimizer)
            t1 = time.monotonic()
            compiled = lowered.compile()
            t2 = time.monotonic()
            mem = compiled.memory_analysis()
            cost_scanned = _cost_vector(compiled)
            hlo = compiled.as_text()
            print(mem)
            print({k: cost_scanned[k] for k in ("flops", "bytes_accessed")})
            rec.update(
                ok=True,
                lower_s=round(t1 - t0, 2),
                compile_s=round(t2 - t1, 2),
                memory={
                    "argument_bytes": mem.argument_size_in_bytes,
                    "output_bytes": mem.output_size_in_bytes,
                    "temp_bytes": mem.temp_size_in_bytes,
                    "alias_bytes": mem.alias_size_in_bytes,
                },
                cost_scanned=cost_scanned,
            )
            # ---- pass 2: unrolled depth variants -> true per-device cost
            if not skip_cost:
                t3 = time.monotonic()
                rec["cost"] = _extrapolated_cost(arch, shape, mesh, rules,
                                                 optimizer)
                rec["cost_s"] = round(time.monotonic() - t3, 2)
        if keep_hlo:
            rec["hlo"] = hlo
    except Exception as e:  # a failure here is a bug in the system
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod"])
    ap.add_argument("--optimizer", default=None)
    ap.add_argument("--rules", nargs="*", default=[],
                    help="logical=mesh overrides, e.g. cache_seq=model "
                         "or d_ff=data,model ('' = replicate)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-cost", action="store_true",
                    help="memory/compile pass only (skip unrolled cost pass)")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--cfg", nargs="*", default=[],
                    help="ModelConfig overrides, e.g. moe_force_weight_gather=true")
    args = ap.parse_args(argv)

    GRAD_ACCUM[0] = args.grad_accum
    for kv in args.cfg:
        k, _, v = kv.partition("=")
        if v.lower() in ("true", "false"):
            val = v.lower() == "true"
        else:
            try:
                val = int(v)
            except ValueError:
                val = v
        CFG_OVERRIDES[k] = val

    overrides = {}
    for kv in args.rules:
        k, _, v = kv.partition("=")
        axes = tuple(x for x in v.split(",") if x)
        overrides[k] = axes if len(axes) > 1 else (axes[0] if axes else None)

    rec = run_cell(args.arch, args.shape, args.mesh, overrides,
                   args.optimizer, skip_cost=args.skip_cost)
    print(json.dumps({k: v for k, v in rec.items() if k != "hlo"}, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    sys.exit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
