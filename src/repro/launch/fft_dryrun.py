import os
# compile-only on forced host devices: never claim an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Dry-run + roofline for the paper's own workload on the production mesh.

Variants measured (each lower+compile on the 256-chip and 512-chip meshes,
costs are exact — no scans in this path):

  segmented      the paper's map-only regime: batch of independent FFTs,
                 zero collectives (the baseline reproduction)
  dist_base      distributed four-step, natural output order, elementwise
                 jnp twiddle (paper-faithful cluster FFT: their §VI plan)
  dist_fused     + twiddle fused into the Pallas leaf kernel epilogue
                 (computed on the fly from iota: no HBM table, no extra
                 output round-trip)
  dist_transposed + natural_order=False (skip all_to_all #3, FFTW
                 TRANSPOSED_OUT) for convolution-style consumers
  pencil2d       2-D pencil decomposition of an equal-point image
                 (default 16384 x 16384 = 2^28 points): rows sharded,
                 local axis passes, ONE transpose exchange — a third of
                 dist_base's collective bytes for the same point count

An `ooc_2^K_analytic` record carries the out-of-core factorization and IO
cost model at the terabyte-class point (default 2^34 points = 128 GiB
under a 1 GiB budget): io_bytes/shuffle_bytes/working_set plus the
seconds predicted by the shared ThrottledStore disk model.

Each distributed record also carries the plan's exposed-vs-total
collective split, and a `dist_overlap*_analytic` record reports the
PREDICTED win of the chunked ppermute pipeline (DESIGN.md §8) from the
analytic cost model alone — the overlapped executable is never compiled
here: its ring unrolls D-1 collective-permutes per slab, which at 512
devices is exactly the regime `overlap="auto"` declines (the same reason
this dryrun would take hours to lower it). benchmarks/bench_distributed.py
compiles + executes the pipeline on the 8-device mesh.

  PYTHONPATH=src python -m repro.launch.fft_dryrun --n 268435456
"""

import argparse
import json
import math

import jax
import jax.numpy as jnp

import repro.fft as fft_api
from repro.launch.hlo_analysis import collective_stats, cost_analysis_dict
from repro.launch.mesh import make_production_mesh

PEAK, HBM, ICI = 197e12, 819e9, 50e9


def measure(plan, args_abs, name):
    """Lower+compile one ExecutablePlan's jit'd callable; exact XLA costs."""
    lowered = plan.executable.lower(*args_abs)
    compiled = lowered.compile()
    cost = cost_analysis_dict(compiled.cost_analysis())
    mem = compiled.memory_analysis()
    colls = collective_stats(compiled.as_text())
    flops = cost.get("flops", 0.0)
    byts = cost.get("bytes accessed", 0.0)
    rec = {
        "name": name,
        "flops": flops,
        "bytes": byts,
        "collective_bytes": colls["total_bytes"],
        "a2a_bytes": colls["all-to-all"]["bytes"],
        "temp_bytes": mem.temp_size_in_bytes,
        "compute_s": flops / PEAK,
        "memory_s": byts / HBM,
        "collective_s": colls["total_bytes"] / ICI,
        # the plan's analytic model next to XLA's measured costs, so the
        # two stay honest against each other in the trajectory
        "plan_flops": plan.flops,
        "plan_hbm_bytes": plan.hbm_bytes,
        "plan_collective_bytes": plan.collective_bytes,
        "plan_exposed_collective_bytes": plan.exposed_collective_bytes,
    }
    rec["bound"] = max(("compute_s", "memory_s", "collective_s"),
                       key=lambda k: rec[k])
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 28,
                    help="global FFT length (distributed variants)")
    ap.add_argument("--n2d", type=int, nargs=2, default=[1 << 14, 1 << 14],
                    help="global image shape (pencil2d variant)")
    ap.add_argument("--n3d", type=int, nargs=3,
                    default=[1 << 10, 1 << 10, 1 << 8],
                    help="global volume shape (pencil3d variant; axes 0 "
                         "and 1 shard over the (data, model) mesh axes)")
    ap.add_argument("--tune", action="store_true",
                    help="run the measuring autotuner (analytic measurer "
                         "— nothing executes here) on the pencil2d spec "
                         "and report the winner + wisdom stats")
    ap.add_argument("--wisdom-path", default=None,
                    help="wisdom file for --tune (default "
                         "~/.cache/repro_fft/wisdom.json)")
    ap.add_argument("--seg-batch", type=int, default=1 << 15)
    ap.add_argument("--seg-len", type=int, default=4096)
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod"])
    ap.add_argument("--ooc-log2-n", type=int, default=34,
                    help="out-of-core analytic record: log2 points")
    ap.add_argument("--ooc-budget-mb", type=int, default=1024,
                    help="out-of-core analytic record: budget in MiB")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.mesh == "multi_pod")
    axes = tuple(mesh.shape.keys())
    sds = jax.ShapeDtypeStruct
    recs = []

    # paper regime: segmented map-only
    seg = sds((args.seg_batch, args.seg_len), jnp.float32)
    p_seg = fft_api.plan(kind="c2c", n=args.seg_len,
                         batch_shape=(args.seg_batch,), mesh=mesh,
                         placement="segmented", axes=axes)
    recs.append(measure(p_seg, (seg, seg), "segmented"))

    # distributed four-step variants
    sig = sds((args.n,), jnp.float32)
    for name, kw in (
        ("dist_base", dict(natural_order=True, fuse_twiddle=False)),
        ("dist_fused", dict(natural_order=True, fuse_twiddle=True)),
        ("dist_transposed", dict(natural_order=False, fuse_twiddle=True)),
    ):
        p = fft_api.plan(kind="c2c", n=args.n, mesh=mesh,
                         placement="distributed", axes=axes, overlap="off",
                         **kw)
        recs.append(measure(p, (sig, sig), name))

    # 2-D pencil: same machinery, one exchange leg instead of three —
    # the plan's collective counter is the headline (a third of
    # dist_base's bytes at the same point count, DESIGN.md §9)
    shape2d = tuple(args.n2d)
    img = sds(shape2d, jnp.float32)
    p_pencil = fft_api.plan(kind="c2c", shape=shape2d, mesh=mesh,
                            placement="distributed", axes=axes,
                            overlap="off")
    recs.append(measure(p_pencil, (img, img), "pencil2d"))

    if args.tune:
        # measured plan selection for the pencil spec — the analytic
        # measurer ranks candidates on the cost model without executing
        # anything (this is a dryrun); winners persist as wisdom so a
        # real launch with --tune re-plans with zero measurements
        from repro.fft import tuner
        cfg = tuner.TuneConfig(measurer="analytic")
        knobs, trep = tuner.tune(
            kind="c2c", shape=shape2d, mesh=mesh, axes=axes,
            num_devices=math.prod(mesh.shape[a] for a in axes),
            axis_sizes=tuple(mesh.shape[a] for a in axes),
            placement="distributed", wisdom_path=args.wisdom_path,
            config=cfg)
        recs.append({
            "name": "pencil2d_tuned", "analytic_only": True,
            "winner": knobs, "wisdom_hit": trep.wisdom_hit,
            "candidates": len(trep.candidates),
            "disagreement": trep.disagreement,
            "tune_stats": tuner.tune_stats(),
        })

    # 3-D pencil: one mesh axis per sharded volume axis, ndim-1 == 2
    # re-pencil exchange legs (arXiv:2202.12756) — the per-leg
    # collective split is the record's headline
    shape3d = tuple(args.n3d)
    axes3 = axes[-2:]
    vol = sds(shape3d, jnp.float32)
    p_pencil3 = fft_api.plan(kind="c2c", shape=shape3d, mesh=mesh,
                             placement="distributed", axes=axes3,
                             overlap="off")
    rec3 = measure(p_pencil3, (vol, vol), "pencil3d")
    rec3["n_exchanges"] = p_pencil3.dist.n_exchanges
    rec3["plan_per_leg_collective_bytes"] = list(
        p_pencil3.per_leg_collective_bytes)
    recs.append(rec3)

    # predicted overlap win, analytic only (module docstring): plan the
    # chunked pipeline — never lower it — and report what its cost model
    # says the monolithic path leaves exposed on the ICI critical path
    from repro.core.fft.distributed import plan_distributed
    dp = plan_distributed(args.n, math.prod(mesh.shape[a] for a in axes))
    chunks = min(4, dp.n1 // dp.d, dp.n2 // dp.d)  # valid for any --n
    p_ov = fft_api.plan(kind="c2c", n=args.n, mesh=mesh,
                        placement="distributed", axes=axes,
                        natural_order=True, fuse_twiddle=True,
                        overlap=chunks)
    recs.append({
        "name": f"dist_overlap{chunks}_analytic",
        "analytic_only": True,
        "plan_collective_bytes": p_ov.collective_bytes,
        "plan_exposed_collective_bytes": p_ov.exposed_collective_bytes,
        "plan_hidden_collective_bytes": p_ov.hidden_collective_bytes,
        "collective_s": p_ov.collective_bytes / ICI,
        "exposed_collective_s": p_ov.exposed_collective_bytes / ICI,
        "predicted_overlap_win_s": p_ov.hidden_collective_bytes / ICI,
    })

    # out-of-core terabyte point: factorization + IO cost model only (the
    # operand would be 8*2^ooc-log2-n bytes of disk; the streamed run lives
    # in benchmarks/bench_outofcore.py at verifiable sizes). Disk-model
    # seconds use the shared ThrottledStore rate so the record is
    # comparable with bench_pipeline's throughput numbers.
    from repro.core.pipeline.testing import DISK_MB_S
    f_ooc = fft_api.factor_out_of_core(1 << args.ooc_log2_n,
                                       args.ooc_budget_mb << 20)
    disk_bytes_s = DISK_MB_S * (1 << 20)
    recs.append({
        "name": f"ooc_2^{args.ooc_log2_n}_analytic",
        "analytic_only": True,
        **f_ooc.as_dict(),
        "budget_bytes": args.ooc_budget_mb << 20,
        "disk_model_mb_s": DISK_MB_S,
        "disk_model_s": f_ooc.io_bytes / disk_bytes_s,
    })

    for r in recs:
        print(json.dumps(r))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"n": args.n, "mesh": args.mesh, "variants": recs}, f,
                      indent=1)


if __name__ == "__main__":
    main()
