"""Chip smoke test: the FFT engine's main paths, once each, on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the cross-chip paths on four chips

One chip runs four phases through the entry points a user calls:

  kernels       ``repro.fft.plan(...).execute*`` with impl="matfft": c2c
                leaves at n = 256 .. 16384, the level-1 zero-copy
                four-step at 2^20, and r2c at 1024 and 32768 — 256 MiB
                per call;
  paper's job   the pipelined ``MapOnlyJob`` of ``launch/fft_job.py`` over
                a 2 GiB capture of interleaved complex64 in 512 MiB blocks;
  service       ``FftService`` under ~64 requests over three spec keys;
  out-of-core   a 2^26-point c2c streamed through a ``BlockStore`` under a
                64 MiB working-set budget.

``--chips 4`` runs only what exists across chips, each compared with the
one-chip plan: the segmented batch, the distributed 1-D four-step at 2^26
(monolithic and overlapped exchanges) and the 3-D pencil at 256^3.

Every output is compared with float64 numpy: the per-row relative L2
error must stay under TOL, which a single bf16 MXU pass would exceed. Each
plan must be compiled (``interpret=False``) with impl="matfft"; no plan may
downgrade, no block may retry or fail, and every service request must
complete. All data comes from ``--seed``; the work directory is removed
at the end. The script exits non-zero at the first failed phase, and at
once when JAX finds no TPU. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOL = 1e-4            # max per-row relative L2 error; bf16 gives ~1e-3
SAMPLE_ROWS = 64      # rows per output checked against numpy
KERNEL_BYTES = 256 << 20
KERNEL_C2C = (256, 1024, 4096, 16384, 1 << 20)
KERNEL_R2C = (1024, 32768)
JOB_FFT_LEN = 1024
JOB_SEGMENTS_PER_BLOCK = 65536   # 512 MiB blocks at fft_len=1024
JOB_BLOCKS = 4                   # a 2 GiB capture
SERVICE_KEYS = (("c2c", 1024, 64), ("c2c", 16384, 4), ("r2c", 4096, 16))
SERVICE_REQUESTS = 64
OOC_LOG2_N = 26
OOC_BUDGET = 64 << 20
MULTI_SEGMENTS = 4 * 32768       # segmented phase: 256 MiB of 1024-point
MULTI_LOG2_N = 26
MULTI_PENCIL = (256, 256, 256)


class SmokeFailure(Exception):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def rel_l2(got: np.ndarray, want: np.ndarray) -> float:
    """Max over rows of ||got - want|| / ||want|| (last axis)."""
    got = np.asarray(got, np.complex128).reshape(-1, want.shape[-1])
    want = np.asarray(want, np.complex128).reshape(got.shape)
    num = np.linalg.norm(got - want, axis=-1)
    den = np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
    return float(np.max(num / den))


def _report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _check_err(phase: str, case: str, err: float, t0: float, **fields):
    _report(phase, case=case, max_rel_l2=err, tol=TOL,
            wall_s=time.monotonic() - t0, **fields)
    _check(np.isfinite(err) and err < TOL,
           f"{phase} {case}: relative L2 error {err} >= {TOL}")


def _check_plans() -> int:
    """Every plan the process built is compiled and uses matfft."""
    from repro.fft import planner
    plans = list(planner._PLAN_CACHE.values())
    for p in plans:
        _check(p.spec.interpret is False,
               f"plan {p!r} runs in interpret mode")
        _check(p.spec.impl == "matfft", f"plan {p!r} is not impl=matfft")
    return len(plans)


def _check_events() -> None:
    from repro.core.resilience import events
    _check(not events("plan_downgrade"),
           f"plan downgraded: {events('plan_downgrade')}")
    lost = [e for e in events("service_degrade")
            if e.get("reason") == "device_loss"]
    _check(not lost, f"service saw device loss: {lost}")


def _rows_sample(rng, rows: int) -> np.ndarray:
    return np.sort(rng.choice(rows, min(rows, SAMPLE_ROWS), replace=False))


def _planar(rng, shape):
    return (rng.standard_normal(shape, dtype=np.float32),
            rng.standard_normal(shape, dtype=np.float32))


def _take(y, idx) -> np.ndarray:
    return np.asarray(y)[idx]


# ---------------------------------------------------------------------------
# one chip


def phase_kernels(rng) -> None:
    import repro.fft as fft_api
    for n in KERNEL_C2C:
        t0 = time.monotonic()
        rows = KERNEL_BYTES // (8 * n)
        p = fft_api.plan(kind="c2c", n=n, batch_shape=(rows,),
                         impl="matfft", fallback="error")
        xr, xi = _planar(rng, (rows, n))
        yr, yi = p.execute(xr, xi)
        idx = _rows_sample(rng, rows)
        want = np.fft.fft(xr[idx].astype(np.float64) + 1j * xi[idx])
        err = rel_l2(_take(yr, idx) + 1j * _take(yi, idx), want)
        _check_err("kernels", f"c2c n={n}", err, t0, rows=rows,
                   levels=p.levels, interpret=p.spec.interpret)
    for n in KERNEL_R2C:
        t0 = time.monotonic()
        rows = KERNEL_BYTES // (4 * n)
        p = fft_api.plan(kind="r2c", n=n, batch_shape=(rows,),
                         impl="matfft", fallback="error")
        x = rng.standard_normal((rows, n), dtype=np.float32)
        yr, yi = p.execute_real(x)
        idx = _rows_sample(rng, rows)
        want = np.fft.rfft(x[idx].astype(np.float64))
        err = rel_l2(_take(yr, idx) + 1j * _take(yi, idx), want)
        _check_err("kernels", f"r2c n={n}", err, t0, rows=rows,
                   levels=p.levels, interpret=p.spec.interpret)


def phase_job(rng, work: Path) -> None:
    from repro.core.pipeline import BlockStore, JobConfig
    from repro.core.pipeline.records import segment_block_bytes
    from repro.launch.fft_job import run_job
    t0 = time.monotonic()
    n_seg = JOB_BLOCKS * JOB_SEGMENTS_PER_BLOCK
    sig = rng.standard_normal((n_seg, JOB_FFT_LEN, 2), dtype=np.float32)
    store = BlockStore(work / "in", block_bytes=segment_block_bytes(
        JOB_FFT_LEN, JOB_SEGMENTS_PER_BLOCK))
    store.put_bytes(sig)
    cfg = JobConfig(coalesce=2, inflight=2, speculation=False)
    job, stats, stage_s = run_job(store, work / "out",
                                  fft_len=JOB_FFT_LEN, impl="matfft",
                                  cfg=cfg, pipelined=True)
    _check(stats.failed_blocks == [], f"failed blocks {stats.failed_blocks}")
    _check(stats.retries == 0, f"{stats.retries} block retries")
    _check(stats.blocks_done == len(store.blocks),
           f"{stats.blocks_done} of {len(store.blocks)} blocks done")
    shutil.rmtree(work / "in")  # keep at most two copies on disk
    merged = work / "merged.bin"
    nbytes = job.merge(merged)
    _check(nbytes == sig.nbytes,
           f"merged {nbytes} bytes, captured {sig.nbytes}")
    shutil.rmtree(work / "out")
    out = np.memmap(merged, dtype=np.float32, mode="r").reshape(sig.shape)
    err = 0.0
    for b in range(JOB_BLOCKS):
        idx = b * JOB_SEGMENTS_PER_BLOCK + _rows_sample(
            rng, JOB_SEGMENTS_PER_BLOCK)
        x = sig[idx].astype(np.float64)
        want = np.fft.fft(x[..., 0] + 1j * x[..., 1])
        got = out[idx, :, 0] + 1j * out[idx, :, 1].astype(np.float64)
        err = max(err, rel_l2(got, want))
    del out
    _check_err("paper_job", f"{sig.nbytes >> 20} MiB, fft_len="
               f"{JOB_FFT_LEN}", err, t0, blocks=len(store.blocks),
               batches=stats.batches, retries=stats.retries,
               stage_s={k: round(v, 3) for k, v in stage_s.items()})
    merged.unlink()


def phase_service(rng) -> None:
    from repro.serve import FftService
    t0 = time.monotonic()
    svc = FftService(impl="matfft", degrade=False, coalesce=4,
                     queue_depth=4 * SERVICE_REQUESTS)
    try:
        svc.warmup([(kind, (n,), rows) for kind, n, rows in SERVICE_KEYS])
        sent = []
        for i in range(SERVICE_REQUESTS):
            kind, n, rows = SERVICE_KEYS[i % len(SERVICE_KEYS)]
            if kind == "c2c":
                ops = _planar(rng, (rows, n))
            else:
                ops = (rng.standard_normal((rows, n), dtype=np.float32),)
            sent.append((kind, ops, svc.submit(kind, *ops)))
        errs = {}
        for kind, ops, t in sent:
            yr, yi = t.result(timeout=600)
            x = ops[0].astype(np.float64)
            want = (np.fft.fft(x + 1j * ops[1]) if kind == "c2c"
                    else np.fft.rfft(x))
            key = f"{kind} n={x.shape[-1]}"
            errs[key] = max(errs.get(key, 0.0),
                            rel_l2(np.asarray(yr) + 1j * np.asarray(yi),
                                   want))
    finally:
        svc.close(drain=True)
    snap = svc.stats.snapshot()
    _check(snap["completed"] == SERVICE_REQUESTS,
           f"{snap['completed']} of {SERVICE_REQUESTS} requests completed")
    _check(snap["shed"] == 0 and snap["rejected_total"] == 0
           and snap["failed"] == 0 and snap["retries"] == 0,
           f"service shed/rejected/failed/retried: {snap}")
    for key, err in errs.items():
        _check_err("service", key, err, t0,
                   requests=SERVICE_REQUESTS, batches=snap["batches"])


def phase_out_of_core(rng, work: Path) -> None:
    import repro.fft as fft_api
    from repro.core.fft.outofcore import corner_turn
    from repro.core.pipeline import BlockStore
    t0 = time.monotonic()
    n = 1 << OOC_LOG2_N
    factors = fft_api.factor_out_of_core(n, OOC_BUDGET)
    store = BlockStore(work / "ooc_in",
                       block_bytes=min(factors.pass1_panel_bytes, 1 << 22))
    sig = rng.standard_normal((n, 2), dtype=np.float32)
    store.put_bytes(sig)
    p = fft_api.plan(kind="c2c", n=n, placement="out_of_core", store=store,
                     work_dir=work / "ooc", impl="matfft",
                     budget_bytes=OOC_BUDGET)
    stats = p.execute()
    _check(stats.pass1.retries == 0 and stats.pass2.retries == 0,
           f"out-of-core retries: {stats.as_dict()}")
    _check(not stats.pass1.failed_blocks and not stats.pass2.failed_blocks,
           "out-of-core failed blocks")
    merged = work / "ooc.bin"
    _check(p.merge(merged) == sig.nbytes, "out-of-core merge size")
    out = np.fromfile(merged, dtype=np.float32).reshape(n, 2)
    want = corner_turn(np.fft.fft(corner_turn(
        sig[:, 0] + 1j * sig[:, 1].astype(np.float64), factors)), factors)
    err = rel_l2(out[:, 0] + 1j * out[:, 1].astype(np.float64), want)
    _check_err("out_of_core", f"n=2^{OOC_LOG2_N}", err, t0,
               budget_bytes=OOC_BUDGET, n1=factors.n1, n2=factors.n2)
    shutil.rmtree(work / "ooc")
    shutil.rmtree(work / "ooc_in")
    merged.unlink()


# ---------------------------------------------------------------------------
# four chips


def _check_spread(name: str, y, count: int) -> None:
    devices = {s.device for s in y.addressable_shards}
    _check(len(devices) == count and not y.sharding.is_fully_replicated,
           f"{name}: output shards on {len(devices)} devices, replicated="
           f"{y.sharding.is_fully_replicated}")


def phase_multi(rng, count: int) -> None:
    import repro.fft as fft_api
    from repro import compat
    line = compat.make_mesh((count,), ("data",))
    grid = compat.make_mesh((2, count // 2), ("data", "model"))

    t0 = time.monotonic()
    rows, n = MULTI_SEGMENTS, JOB_FFT_LEN
    xr, xi = _planar(rng, (rows, n))
    p = fft_api.plan(kind="c2c", n=n, batch_shape=(rows,), mesh=line,
                     placement="segmented", impl="matfft")
    yr, yi = p.execute(xr, xi)
    _check_spread("segmented", yr, count)
    one = fft_api.plan(kind="c2c", n=n, batch_shape=(rows,), impl="matfft")
    wr, wi = one.execute(xr, xi)
    idx = _rows_sample(rng, rows)
    got = _take(yr, idx) + 1j * _take(yi, idx)
    vs_one = rel_l2(got, _take(wr, idx) + 1j * _take(wi, idx))
    err = rel_l2(got, np.fft.fft(xr[idx].astype(np.float64) + 1j * xi[idx]))
    _check_err("multi", f"segmented c2c {rows}x{n}", err, t0,
               vs_one_chip=vs_one)
    _check(vs_one < TOL, f"segmented vs one chip: {vs_one}")

    n = 1 << MULTI_LOG2_N
    xr, xi = _planar(rng, (n,))
    want = np.fft.fft(xr.astype(np.float64) + 1j * xi)
    one = fft_api.plan(kind="c2c", n=n, impl="matfft")
    wr, wi = (np.asarray(a) for a in one.execute(xr, xi))
    for overlap in ("off", 4):
        t0 = time.monotonic()
        p = fft_api.plan(kind="c2c", n=n, mesh=line, placement="distributed",
                         overlap=overlap, impl="matfft")
        yr, yi = p.execute(xr, xi)
        _check_spread(f"distributed overlap={overlap}", yr, count)
        got = np.asarray(yr) + 1j * np.asarray(yi)
        vs_one = rel_l2(got, wr + 1j * wi)
        _check_err("multi", f"distributed c2c n=2^{MULTI_LOG2_N} "
                   f"overlap={overlap}", rel_l2(got, want), t0,
                   vs_one_chip=vs_one)
        _check(vs_one < TOL, f"distributed vs one chip: {vs_one}")

    t0 = time.monotonic()
    shape = MULTI_PENCIL
    xr, xi = _planar(rng, shape)
    p = fft_api.plan(kind="c2c", shape=shape, mesh=grid,
                     placement="distributed", impl="matfft")
    yr, yi = p.execute(xr, xi)
    _check_spread("pencil", yr, count)
    one = fft_api.plan(kind="c2c", shape=shape, impl="matfft")
    wr, wi = one.execute(xr, xi)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    vs_one = rel_l2(got.ravel()[None], (np.asarray(wr) + 1j * np.asarray(
        wi)).ravel()[None])
    want = np.fft.fftn(xr.astype(np.float64) + 1j * xi)
    _check_err("multi", f"pencil c2c {'x'.join(map(str, shape))}",
               rel_l2(got.ravel()[None], want.ravel()[None]), t0,
               vs_one_chip=vs_one, grid=list(p.dist.grid))
    _check(vs_one < TOL, f"pencil vs one chip: {vs_one}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.core.resilience import clear_events
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    clear_events()
    print(json.dumps({
        "device_kind": dev.device_kind, "device_count": len(devices),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache": cache}), flush=True)

    rng = np.random.default_rng(args.seed)
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    t0 = time.monotonic()
    try:
        if args.chips == 4:
            phase_multi(rng, args.chips)
        else:
            phase_kernels(rng)
            phase_job(rng, work)
            phase_service(rng)
            phase_out_of_core(rng, work)
        _check_events()
        plans = _check_plans()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    _report("done", plans=plans, wall_s=time.monotonic() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
