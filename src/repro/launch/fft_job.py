"""The paper's workload as a launcher: block-distributed FFT over a file.

  PYTHONPATH=src python -m repro.launch.fft_job --size-mb 64 --fft-len 1024 \
      --workers 4 --work-dir /tmp/fft_job --pipelined --coalesce 4

Mirrors the paper's Figure 1 flow: copy-in (split into blocks) -> map-only
batched FFT per block -> direct output writes -> getmerge. Two execution
modes over the same store:

  * serial (default): the classic one-thread-per-block map task, each
    attempt doing read -> decode -> H2D -> execute -> sync -> D2H ->
    encode -> write in sequence;
  * --pipelined: the overlapped stream executor (core/pipeline/stream.py)
    with ``--coalesce`` same-shaped blocks per device batch and an
    ``--inflight`` launch window, so device compute hides behind block I/O.

Both report per-stage clocks (read, launch, device_wait, d2h, verify,
write; the stream adds gather), each the summed duration of the stage's
``fft.stream.<stage>`` or ``fft.serial.<stage>`` spans, plus the paper's
Amdahl/runtime-model prediction.
"""

from __future__ import annotations

import argparse
import json
import threading
import time
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np
import jax.numpy as jnp

from repro.core.amdahl import ClusterModel, calibrate_unit_time, fit_parallel_fraction
from repro.core.pipeline import (BlockStore, JobConfig, MapOnlyJob,
                                 SegmentFFTTransform, block_of_segments,
                                 segments_of_block)
from repro.core.pipeline.records import segment_block_bytes
import repro.fft as fft_api
from repro import spans
from repro.launch.compile_cache import enable_compile_cache

#: the serial path's stage clocks: the stream's (core/pipeline/stream.py)
#: less ``gather``, which a one-block task does not do
SERIAL_STAGES = ("read", "launch", "device_wait", "d2h", "verify", "write")


class _TimedStore:
    """Serial-mode shim: clocks block file I/O into the shared stage dict
    so the serial path's "read"/"write" totals cover the same work as the
    stream executor's (file I/O happens inside MapOnlyJob._attempt, out of
    map_fn's reach)."""

    def __init__(self, store: BlockStore, stage):
        self._store = store
        self._stage = stage

    def __getattr__(self, name):
        return getattr(self._store, name)

    def read_block(self, index: int, verify: bool = True) -> bytes:
        with self._stage("read"):
            return self._store.read_block(index, verify)

    def write_output_block(self, out_dir, index: int, data) -> None:
        with self._stage("write"):
            self._store.write_output_block(out_dir, index, data)


def serial_map_fn(fft_len: int, impl: str, stage, verify: str = "off",
                  tune: bool = False, wisdom_path=None):
    """The synchronous per-block map task, with per-stage clocks.

    ``stage(name)`` is the context that times one stage. Stage names match
    the stream executor's so the two paths are comparable ("read"/"write"
    also accumulate the block file I/O, via `_TimedStore`).
    """

    def map_fn(data: bytes, idx: int) -> bytes:
        with stage("read"):
            re, im = segments_of_block(data, fft_len)
        with stage("launch"):
            # every same-shaped block hits the process-level plan cache:
            # the jit'd callable is built once, the cufftPlanMany
            # amortization
            p = fft_api.plan(kind="c2c", n=fft_len,
                             batch_shape=re.shape[:-1], impl=impl,
                             verify=verify, tune=tune,
                             wisdom_path=wisdom_path)
            yr, yi = p.execute(jnp.asarray(re), jnp.asarray(im))
        with stage("device_wait"):
            jax.block_until_ready((yr, yi))  # the per-block sync
        with stage("d2h"):
            yr, yi = np.asarray(yr), np.asarray(yi)
        with stage("write"):
            return block_of_segments(yr, yi)

    return map_fn


def parseval_verify_fn(fft_len: int):
    """Serial-mode ABFT hook (`JobConfig.verify_fn`): block-aggregate
    Parseval over the map output — every segment is length fft_len, so
    the whole block must carry fft_len x its input energy."""
    from repro.core.resilience import verify as abft

    def verify_fn(data: bytes, out: bytes, index: int) -> None:
        re, im = segments_of_block(data, fft_len)
        yr, yi = segments_of_block(out, fft_len)
        abft.check_parseval(abft.energy(re, im), abft.energy(yr, yi),
                            fft_len, "f32", site="maponly.attempt",
                            index=index)

    return verify_fn


def run_job(store: BlockStore, out_dir, *, fft_len: int, impl: str,
            cfg: JobConfig, pipelined: bool, verify: str = "off",
            tune: bool = False, wisdom_path=None):
    """Run the FFT job serial or pipelined; returns (job, stats, stage_s)."""
    if pipelined:
        job = MapOnlyJob(store, out_dir, config=cfg, pipelined=True,
                         transform=SegmentFFTTransform(fft_len, impl=impl,
                                                       verify=verify))
        stats = job.run()
        return job, stats, dict(stats.stage_s)
    stage_s = dict.fromkeys(SERIAL_STAGES, 0.0)
    lock = threading.Lock()  # map tasks run on the job's worker pool

    def stage(name: str):
        return spans.timed(f"fft.serial.{name}", stage_s, name, lock)

    if verify != "off":
        check = parseval_verify_fn(fft_len)

        def verify_fn(data: bytes, out: bytes, index: int) -> None:
            with stage("verify"):
                check(data, out, index)

        cfg = replace(cfg, verify_fn=verify_fn)
    job = MapOnlyJob(_TimedStore(store, stage), out_dir,
                     serial_map_fn(fft_len, impl, stage, verify,
                                   tune=tune, wisdom_path=wisdom_path),
                     config=cfg)
    stats = job.run()
    return job, stats, stage_s


def run_out_of_core(args) -> dict:
    """The >RAM workload: one giant 1-D c2c streamed through the store.

    Ingests 2^log2_n random complex64 samples as a `BlockStore`, builds
    the ``placement="out_of_core"`` plan under ``--budget-mb``, executes
    both streamed passes (crash-resume: re-running the same --work-dir
    picks up from the phase manifests), and getmerges the spectrum.
    """
    work = Path(args.work_dir)
    n = 1 << args.log2_n
    budget = args.budget_mb << 20
    factors = fft_api.factor_out_of_core(n, budget)
    # one job's panel per block, capped at 4 MB: both are powers of two,
    # so the block always tiles the panel
    block_bytes = min(factors.pass1_panel_bytes, 1 << 22)

    t0 = time.monotonic()
    rng = np.random.default_rng(args.seed)
    store = BlockStore(work / "in", block_bytes=block_bytes,
                       replication=args.replication)
    sig = rng.standard_normal((n, 2)).astype(np.float32)
    store.put_bytes(sig.tobytes())
    del sig
    t_put = time.monotonic() - t0

    injector = None
    if args.faults:
        from repro.core.resilience import FaultInjector, FaultPlan
        injector = FaultInjector(
            FaultPlan.parse(args.faults, num_blocks=len(store.blocks)))
        store.injector = injector
    cfg = JobConfig(readers=args.readers, writers=args.writers,
                    inflight=args.inflight, speculation=False,
                    max_retries=args.max_retries, injector=injector)

    plan = fft_api.plan(kind="c2c", n=n, placement="out_of_core",
                        store=store, work_dir=work / "ooc", impl=args.impl,
                        budget_bytes=budget, job_config=cfg,
                        verify=args.verify, tune=args.tune,
                        wisdom_path=args.wisdom_path)
    t0 = time.monotonic()
    stats = plan.execute()
    t_job = time.monotonic() - t0
    t0 = time.monotonic()
    nbytes = plan.merge(work / "merged.bin")
    t_merge = time.monotonic() - t0
    from repro.core.resilience import events
    return {
        "mode": "out_of_core",
        "verify": args.verify,
        "corruption_detected": len(events("verify_failed")),
        "corruption_recomputed": (stats.pass1.retries + stats.pass2.retries
                                  if stats.pass1 and stats.pass2 else 0),
        "factors": factors.as_dict(),
        "block_bytes": block_bytes,
        "budget_bytes": budget,
        "operand_over_budget_x": round(factors.operand_bytes / budget, 2),
        "copy_in_s": round(t_put, 3),
        "job_s": round(t_job, 3),
        "merge_s": round(t_merge, 3),
        "merged_bytes": nbytes,
        "stats": stats.as_dict(),
        "store": store.stats.as_dict(),
        "faults": injector.summary() if injector is not None else None,
        "plan_cache": fft_api.cache_info(),
        "tuner": _tuner_stats(args.tune),
    }


def _tuner_stats(tune: bool):
    """Wisdom/measurement counters for the report; None when --tune off
    (the tuner module is never imported on the default path)."""
    if not tune:
        return None
    from repro.fft import tuner
    return tuner.tune_stats()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=int, default=64)
    ap.add_argument("--fft-len", type=int, default=1024)
    ap.add_argument("--segments-per-block", type=int, default=2048)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--impl", default="matfft",
                    choices=["matfft", "stockham", "ref"])
    ap.add_argument("--work-dir", default="/tmp/repro_fft_job")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pipelined", action="store_true",
                    help="overlapped stream executor instead of the "
                         "serial per-block map loop")
    ap.add_argument("--coalesce", type=int, default=4,
                    help="same-shaped blocks per device batch (pipelined)")
    ap.add_argument("--inflight", type=int, default=2,
                    help="launched-but-unrealized batch window (pipelined)")
    ap.add_argument("--readers", type=int, default=2,
                    help="prefetch/decode threads (pipelined)")
    ap.add_argument("--writers", type=int, default=2,
                    help="writeback threads (pipelined)")
    ap.add_argument("--replication", type=int, default=1,
                    help="block replicas kept in the store")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="per-block attempt budget")
    ap.add_argument("--faults", default=None,
                    help="deterministic fault schedule to replay "
                         "(core/resilience/faults.py FaultPlan.parse spec: "
                         "'seed=N,rate=R,sites=a+b', inline JSON, or "
                         "@file.json; add kind=corrupt for silent "
                         "bit-rot) — the report then carries retry, "
                         "repair, and injector stats")
    ap.add_argument("--verify", default="off",
                    choices=["off", "parseval", "abft"],
                    help="ABFT invariant verification (DESIGN.md §13): "
                         "parseval checks output energy per unit, abft "
                         "adds a linearity checksum row per batch; "
                         "detections quarantine-and-recompute through "
                         "the retry path and are counted in the report")
    ap.add_argument("--out-of-core", action="store_true",
                    help="run one 2^log2-n-point c2c whose operand lives "
                         "in the BlockStore, streamed under --budget-mb "
                         "(ignores the segment-batch options above)")
    ap.add_argument("--log2-n", type=int, default=20,
                    help="out-of-core transform size, log2 of points")
    ap.add_argument("--budget-mb", type=int, default=16,
                    help="out-of-core working-set budget in MiB")
    ap.add_argument("--tune", action="store_true",
                    help="measuring autotuner (DESIGN.md §14): plan-time "
                         "candidate sweeps pick layout/batch-tile/"
                         "exchange-engine (and the out-of-core panel "
                         "height) by measurement; winners persist as "
                         "wisdom so later runs re-plan with zero "
                         "measurements — the report carries the "
                         "tuned/wisdom-hit/measurement counters")
    ap.add_argument("--wisdom-path", default=None,
                    help="wisdom file for --tune (default "
                         "~/.cache/repro_fft/wisdom.json)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.out_of_core:
        print(json.dumps(run_out_of_core(args), indent=1))
        return

    work = Path(args.work_dir)
    n_seg = args.size_mb * (1 << 20) // (8 * args.fft_len)
    rng = np.random.default_rng(args.seed)

    # --- copy-in (HDFS put) ---
    t0 = time.monotonic()
    sig = rng.standard_normal((n_seg, args.fft_len, 2)).astype(np.float32)
    store = BlockStore(work / "in", block_bytes=segment_block_bytes(
        args.fft_len, args.segments_per_block),
        replication=args.replication)
    store.put_bytes(sig.tobytes())
    t_put = time.monotonic() - t0

    # --- optional deterministic chaos replay ---
    injector = None
    if args.faults:
        from repro.core.resilience import FaultInjector, FaultPlan
        injector = FaultInjector(
            FaultPlan.parse(args.faults, num_blocks=len(store.blocks)))
        store.injector = injector

    # --- map-only FFT job ---
    cfg = JobConfig(workers=args.workers, readers=args.readers,
                    writers=args.writers, coalesce=args.coalesce,
                    inflight=args.inflight, max_retries=args.max_retries,
                    injector=injector)
    t0 = time.monotonic()
    job, stats, stage_s = run_job(store, work / "out", fft_len=args.fft_len,
                                  impl=args.impl, cfg=cfg,
                                  pipelined=args.pipelined,
                                  verify=args.verify, tune=args.tune,
                                  wisdom_path=args.wisdom_path)
    t_job = time.monotonic() - t0
    t0 = time.monotonic()
    nbytes = job.merge(work / "merged.bin")
    t_merge = time.monotonic() - t0

    # --- paper metrics ---
    # NOTE: stage clocks are per-thread sums; in pipelined mode they run
    # concurrently, so these fractions are shares of total STAGE TIME
    # (thread-seconds of work), not a wall-clock split. The device side is
    # launch (H2D copy and enqueue) + device_wait (the wait for the
    # transform); the rest is host work and I/O. The Amdahl model below
    # calibrates on wall time (t_job) and is unaffected.
    fft_s = stage_s.get("launch", 0.0) + stage_s.get("device_wait", 0.0)
    io_s = sum(v for k, v in stage_s.items()
               if k not in ("launch", "device_wait"))
    p_frac = fit_parallel_fraction(io_s, fft_s)
    n = n_seg * args.fft_len
    unit = calibrate_unit_time(n, t_job, servers=1, cores=args.workers,
                               efficiency=1.0)
    model = ClusterModel(unit_time_s=unit)
    stage_total = sum(stage_s.values())
    from repro.core.resilience import events
    print(json.dumps({
        "mode": "pipelined" if args.pipelined else "serial",
        "verify": args.verify,
        "corruption_detected": len(events("verify_failed")),
        "corruption_recomputed": stats.retries,
        "size_mb": args.size_mb,
        "blocks": len(store.blocks),
        "copy_in_s": round(t_put, 3),
        "job_s": round(t_job, 3),
        "merge_s": round(t_merge, 3),
        "merged_bytes": nbytes,
        "stage_s": {k: round(v, 3) for k, v in stage_s.items()},
        "stage_total_s": round(stage_total, 3),
        # >1 means stages genuinely overlapped (wall < sum of stage time)
        "overlap_x": round(stage_total / t_job, 3) if t_job else None,
        "batches": stats.batches,
        "coalesced_blocks": stats.coalesced_blocks,
        "fft_fraction": round(p_frac, 3),
        "io_fraction": round(1 - p_frac, 3),
        "attempts": stats.attempts,
        "speculative": stats.speculative_launches,
        "retries": stats.retries,
        "failed_blocks": stats.failed_blocks,
        "store": store.stats.as_dict(),
        "faults": injector.summary() if injector is not None else None,
        "predicted_s_8_workers": round(model.predict(n, 1, 8), 3),
        "predicted_s_64_workers": round(model.predict(n, 8, 8), 3),
        "plan_cache": fft_api.cache_info(),
        "tuner": _tuner_stats(args.tune),
    }, indent=1))


if __name__ == "__main__":
    main()
