"""End-to-end pipeline benchmark: serial map-only vs the overlapped stream.

The paper's Hadoop layer wins by overlapping map waves with I/O; the
stream executor (core/pipeline/stream.py) makes that overlap explicit —
prefetch readers, coalesced async device batches, writeback workers. This
benchmark runs the SAME block store through three configurations and
records the trajectory in BENCH_pipeline.json:

  * ``serial`` — the synchronous per-block map loop (one worker: read ->
    decode -> H2D -> execute -> sync -> D2H -> encode -> write, nothing
    overlapped). This is the acceptance baseline: the pipelined mode must
    beat its throughput strictly.
  * ``pipelined`` — the stream executor: coalesce=4, inflight=3,
    4 readers / 4 writers.
  * ``maponly_threaded`` — the classic thread-pool map-only job (reported
    for context, not gated: on a many-core host with a hot page cache it
    approximates a parallel memcpy farm; the stream executor's advantages
    — bounded staging memory, one dispatcher feeding the device window,
    coalesced launches — matter on real accelerators where per-thread
    dispatch serializes on the device anyway).

Per-mode metrics: throughput (input MB/s of job wall), per-stage clock
totals (read/gather/launch/device_wait/d2h/verify/write),
``overlap_efficiency`` = max(stage totals)/wall (1.0 = wall collapsed onto
the slowest stage, a perfectly hidden pipeline) and ``overlap_x`` = sum(stage totals)/wall (> 1 proves
compute and I/O genuinely ran concurrently: wall < sum of stage times).
Outputs of all modes must be bitwise identical — coalesced batches and the
remainder tail must not change a single bit.

Both paths are warmed up on a small store first so plan trace+compile time
(benchmarked separately in BENCH_fft.json) doesn't pollute the comparison.
impl="ref" keeps the leaf transform identical-and-cheap on the CPU CI
container — this benchmark measures orchestration, not the kernels.

I/O model: CI scratch space is effectively tmpfs, where a block "read" is
a page-cache memcpy — there is no latency for a pipeline to hide, and on
a 2-core runner a single sequential loop is already near memory-bandwidth
optimal (the paper's regime is the opposite: spinning-disk HDFS at
~100-250 MB/s per spindle against a fast device). `ThrottledStore`
restores that regime deterministically: every block read/write sleeps
bytes / DISK_MB_S, identically for every mode. The sleep stands in for
real device/disk latency, so the gate measures exactly what the tentpole
claims — the stream executor hides I/O latency behind compute and the
serial loop cannot. ``disk_sim_mb_s`` in the JSON records the model.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
from pathlib import Path

from benchmarks.common import make_signal_store
from repro.core.pipeline import JobConfig
from repro.core.pipeline.testing import DISK_MB_S, ThrottledStore
from repro.launch.fft_job import run_job
import repro.fft as fft_api

OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_pipeline.json"

# per-transition manifest fsyncs + atomic block writes hit the filesystem
# hard; on slow/virtual filesystems (9p, overlay) fsync latency noise
# swamps the orchestration signal this benchmark measures. Prefer tmpfs —
# but only when it can actually hold the working set (Docker's default
# /dev/shm is 64MB; a full run needs input + per-mode outputs + merges).


def _scratch() -> Path | None:
    shm = Path("/dev/shm")
    if not shm.is_dir():
        return None
    try:
        st = os.statvfs(shm)
    except OSError:
        return None
    return shm if st.f_bavail * st.f_frsize >= 2 << 30 else None


_SCRATCH = _scratch()

FFT_LEN = 1024
SEGMENTS_PER_BLOCK = 512  # 4 MB blocks
COALESCE = 4
INFLIGHT = 3
IMPL = "ref"
# ThrottledStore / DISK_MB_S: the shared deterministic disk model
# (repro/core/pipeline/testing.py) — same 250 MB/s spindle as before.

MODES = {
    # speculation off for stable timing; it is covered by the test suite
    "serial": dict(pipelined=False,
                   cfg=JobConfig(workers=1, speculation=False)),
    "pipelined": dict(pipelined=True,
                      cfg=JobConfig(readers=4, writers=4, coalesce=COALESCE,
                                    inflight=INFLIGHT, speculation=False,
                                    poll_interval_s=0.005)),
    "maponly_threaded": dict(pipelined=False,
                             cfg=JobConfig(workers=4, speculation=False)),
}


def _run_mode(store, work: Path, mode: str) -> dict:
    out_dir = work / f"out_{mode}"
    if out_dir.exists():
        shutil.rmtree(out_dir)  # fresh manifest: re-run every block
    t0 = time.monotonic()
    job, stats, stage_s = run_job(store, out_dir, fft_len=FFT_LEN, impl=IMPL,
                                  **MODES[mode])
    wall = time.monotonic() - t0
    merged = work / f"merged_{mode}.bin"
    job.merge(merged)
    stage_total = sum(stage_s.values())
    max_stage = max(stage_s.values()) if stage_s else 0.0
    return {
        "wall_s": wall,
        "throughput_mb_s": store.total_bytes / (1 << 20) / wall,
        "stage_s": {k: round(v, 4) for k, v in stage_s.items()},
        "stage_total_s": round(stage_total, 4),
        "overlap_efficiency": round(max_stage / wall, 4) if wall else None,
        "overlap_x": round(stage_total / wall, 4) if wall else None,
        "batches": stats.batches,
        "coalesced_blocks": stats.coalesced_blocks,
        "blocks": stats.blocks_done,
        "merged": merged,
    }


def run(quick: bool = False):
    size_mb = 64 if quick else 128
    iters = 2 if quick else 3
    fft_api.clear_plan_cache()
    with tempfile.TemporaryDirectory(dir=_SCRATCH) as tmp:
        work = Path(tmp)
        # warmup: compile both paths' plans (serial per-block shape +
        # coalesced full-batch shape) on a store of exactly one full batch
        warm_store, _ = make_signal_store(
            work / "warm_in", size_mb=COALESCE * 4, fft_len=FFT_LEN,
            segments_per_block=SEGMENTS_PER_BLOCK)
        warm_store = ThrottledStore.open(warm_store.root)
        for mode in MODES:
            _run_mode(warm_store, work / "warm", mode)

        store, _ = make_signal_store(work / "in", size_mb=size_mb,
                                     fft_len=FFT_LEN,
                                     segments_per_block=SEGMENTS_PER_BLOCK)
        store = ThrottledStore.open(store.root)
        results = {}
        for mode in MODES:
            best = None
            for _ in range(iters):
                r = _run_mode(store, work, mode)
                if best is None or r["wall_s"] < best["wall_s"]:
                    best = r
            results[mode] = best
        merged = {m: results[m].pop("merged").read_bytes() for m in results}
        identical = all(v == merged["serial"] for v in merged.values())

    ser, pipe = results["serial"], results["pipelined"]
    checks = {
        # acceptance: coalesced+overlapped beats the serial map loop
        "pipelined_throughput_gt_serial":
            pipe["throughput_mb_s"] > ser["throughput_mb_s"],
        # acceptance: wall < sum of stage clocks == genuine overlap
        "pipelined_stages_overlap": pipe["overlap_x"] is not None
            and pipe["overlap_x"] > 1.0,
        # the coalesced batches + remainder tail change nothing, bitwise
        "outputs_bitwise_identical": identical,
    }
    doc = {
        "quick": quick,
        "config": {"size_mb": size_mb, "fft_len": FFT_LEN,
                   "segments_per_block": SEGMENTS_PER_BLOCK,
                   "coalesce": COALESCE, "inflight": INFLIGHT, "impl": IMPL,
                   "disk_sim_mb_s": DISK_MB_S},
        **results,
        "speedup_vs_serial_x": round(
            pipe["throughput_mb_s"] / ser["throughput_mb_s"], 3),
        "checks": checks,
        "plan_cache": fft_api.cache_info(),
    }
    OUT_PATH.write_text(json.dumps(doc, indent=1))

    rows = []
    for mode, r in results.items():
        rows.append({
            "name": f"pipeline_{mode}",
            "us_per_call": r["wall_s"] * 1e6,
            "derived": (f"{r['throughput_mb_s']:.1f}MB/s "
                        f"overlap_x={r['overlap_x']} "
                        f"overlap_eff={r['overlap_efficiency']} "
                        f"batches={r['batches']}"),
        })
    rows.append({"name": "pipeline_checks", "us_per_call": 0.0,
                 "derived": " ".join(f"{k}={'PASS' if ok else 'FAIL'}"
                                     for k, ok in checks.items())})
    return rows


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    for row in run(quick=args.quick):
        print(f"{row['name']},{row['us_per_call']:.1f},\"{row['derived']}\"")
    checks = json.loads(OUT_PATH.read_text())["checks"]
    if not all(checks.values()):
        print(f"FAIL: {checks}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
