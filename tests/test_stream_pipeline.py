"""Stream executor behaviour: output parity, coalescing, faults, journal.

The contract under test (core/pipeline/stream.py): the overlapped pipeline
is a drop-in for the serial map loop — bitwise-identical merged output
(including coalesced batches + the remainder tail), the same retry /
speculation / crash-restart semantics, and exactly two cached plans for a
coalesced run (full batch + tail) with zero retraces.
"""

import glob
import json
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.pipeline import (BlockStore, JobConfig, MapOnlyJob,
                                 SegmentFFTTransform, StagingPool)
from repro.core.pipeline.maponly import Manifest, TaskState
from repro.core.pipeline.stream import STAGES, WAITS
from repro.core.pipeline.records import (block_of_segments,
                                         segment_block_bytes,
                                         segments_of_block)
import repro.fft as fft_api

FFT_LEN = 128
SEG_PER_BLOCK = 16


def _signal_store(tmp_path, blocks=6, replication=1):
    rng = np.random.default_rng(7)
    sig = rng.standard_normal(
        (SEG_PER_BLOCK * blocks, FFT_LEN, 2)).astype(np.float32)
    store = BlockStore(tmp_path / "in",
                       block_bytes=segment_block_bytes(FFT_LEN, SEG_PER_BLOCK),
                       replication=replication)
    store.put_bytes(sig.tobytes())
    assert len(store.blocks) == blocks
    return store


def _serial_map_fn(data, idx):
    re, im = segments_of_block(data, FFT_LEN)
    p = fft_api.plan(kind="c2c", n=FFT_LEN, batch_shape=re.shape[:-1],
                     impl="ref")
    yr, yi = p.execute(re, im)
    return block_of_segments(np.asarray(yr), np.asarray(yi))


def _run_serial(store, tmp_path):
    job = MapOnlyJob(store, tmp_path / "out_serial", _serial_map_fn,
                     JobConfig(workers=2))
    job.run()
    job.merge(tmp_path / "serial.bin")
    return (tmp_path / "serial.bin").read_bytes()


# ---------------------------------------------------------------------------
# bitwise parity + coalescing


def test_stream_bitwise_identical_with_tail(tmp_path):
    """coalesce=4 over 6 blocks -> one full batch + one remainder tail."""
    store = _signal_store(tmp_path, blocks=6)
    expect = _run_serial(store, tmp_path)

    job = MapOnlyJob(store, tmp_path / "out_stream",
                     transform=SegmentFFTTransform(FFT_LEN, impl="ref"),
                     # speculation off: a scheduling-stall twin would add
                     # an extra batch and break the exact counts below
                     config=JobConfig(coalesce=4, inflight=2,
                                      speculation=False),
                     pipelined=True)
    stats = job.run()
    job.merge(tmp_path / "stream.bin")
    assert (tmp_path / "stream.bin").read_bytes() == expect
    assert stats.blocks_done == 6
    assert stats.batches == 2  # 4-block batch + 2-block tail
    assert stats.coalesced_blocks == 4
    assert all(v >= 0 for v in stats.stage_s.values())
    # journal fd released after the run (incl. the late-finisher drain)
    assert job.manifest._fh is None


def _traced_fft_spans(trace_dir, run):
    """``run()`` under a profiler session; its result and the ``fft.*``
    host spans of the trace, as (name, seconds, attributes)."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        out = run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = [(e.name, e.duration_ns * 1e-9, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("fft.")]
    return out, spans


def test_stream_spans_are_the_stage_clocks(tmp_path):
    """Every stream stage, wait and launch is a host span in a profiler
    trace, and each stage clock is the sum of its spans' durations."""
    store = _signal_store(tmp_path, blocks=6)
    job = MapOnlyJob(store, tmp_path / "out",
                     transform=SegmentFFTTransform(FFT_LEN, impl="ref"),
                     config=JobConfig(coalesce=4, inflight=2,
                                      speculation=False),
                     pipelined=True)
    stats, spans = _traced_fft_spans(tmp_path / "trace", job.run)
    names = {n for n, _, _ in spans}
    assert names >= {f"fft.stream.{s}" for s in STAGES} | {
        "fft.stream.wait_decoded", "fft.stream.wait_inflight",
        "fft.stream.dispatch", "fft.plan.launch"}
    clocks = [(f"fft.stream.{s}", stats.stage_s[s]) for s in STAGES] + [
        (f"fft.stream.wait_{w}", stats.wait_s[w]) for w in WAITS]
    for name, clock in clocks:
        total = sum(d for n, d, _ in spans if n == name)
        assert abs(total - clock) <= 1e-3 + 0.01 * clock, name
    # one 4-block batch and the 2-block tail, each through its own plan
    dispatches = [a for n, _, a in spans if n == "fft.stream.dispatch"]
    assert sorted(a["blocks"] for a in dispatches) == [2, 4]
    assert all(a["queued"] >= 0 for a in dispatches)
    assert {a["plan"] for n, _, a in spans if n == "fft.plan.launch"} == {
        f"c2c_{FFT_LEN}_b{k * SEG_PER_BLOCK}_ref_donated" for k in (2, 4)}


def test_dispatcher_waits_are_counted(tmp_path):
    store = _signal_store(tmp_path, blocks=5)
    stats = MapOnlyJob(store, tmp_path / "out", _serial_map_fn, JobConfig(),
                       pipelined=True).run()
    assert set(stats.wait_s) == set(WAITS)
    assert all(v >= 0 for v in stats.wait_s.values())
    assert not hasattr(stats, "task_seconds")


def test_serial_launcher_stages_are_spans(tmp_path):
    """The launcher's serial path times the stream's stages, less gather,
    through the same spans (``fft.serial.<stage>``)."""
    from repro.launch.fft_job import SERIAL_STAGES, run_job
    store = _signal_store(tmp_path, blocks=3)
    (job, stats, stage_s), spans = _traced_fft_spans(
        tmp_path / "trace",
        lambda: run_job(store, tmp_path / "out", fft_len=FFT_LEN,
                        impl="ref", cfg=JobConfig(workers=2),
                        pipelined=False, verify="parseval"))
    assert stats.blocks_done == 3 and set(stage_s) == set(SERIAL_STAGES)
    for stage in SERIAL_STAGES:
        total = sum(d for n, d, _ in spans if n == f"fft.serial.{stage}")
        assert total > 0, stage
        assert abs(total - stage_s[stage]) <= 1e-3 + 0.01 * stage_s[stage]


def test_stream_mapfn_path_identical(tmp_path):
    """pipelined=True with a classic map_fn matches the serial output."""
    store = _signal_store(tmp_path, blocks=5)
    expect = _run_serial(store, tmp_path)
    job = MapOnlyJob(store, tmp_path / "out_mapfn", _serial_map_fn,
                     JobConfig(), pipelined=True)
    stats = job.run()
    job.merge(tmp_path / "mapfn.bin")
    assert (tmp_path / "mapfn.bin").read_bytes() == expect
    assert stats.blocks_done == 5
    assert stats.batches == 5  # opaque bytes never coalesce


def test_coalescing_uses_exactly_two_plans_zero_retrace(tmp_path):
    """8 = 4+4 blocks -> ONE cached plan; 6 = 4+2 -> full + tail plans.

    Each plan must be traced exactly once however many batches reuse it
    (the cufftPlanMany amortization the stream dispatcher exists to feed).
    """
    store = _signal_store(tmp_path, blocks=8)
    fft_api.clear_plan_cache()
    job = MapOnlyJob(store, tmp_path / "out",
                     transform=SegmentFFTTransform(FFT_LEN, impl="ref"),
                     config=JobConfig(coalesce=4, inflight=2,
                                      speculation=False),
                     pipelined=True)
    job.run()
    info = fft_api.cache_info()
    assert info["size"] == 1, info  # both batches share the full plan
    full = fft_api.plan(kind="c2c", n=FFT_LEN,
                        batch_shape=(4 * SEG_PER_BLOCK,), impl="ref")
    assert full.trace_counts["forward"] == 1

    store2 = _signal_store(tmp_path / "t2", blocks=6)
    fft_api.clear_plan_cache()
    job2 = MapOnlyJob(store2, tmp_path / "out2",
                      transform=SegmentFFTTransform(FFT_LEN, impl="ref"),
                      config=JobConfig(coalesce=4, inflight=2,
                                       speculation=False),
                      pipelined=True)
    job2.run()
    info = fft_api.cache_info()
    assert info["size"] == 2, info  # full batch + remainder tail
    for rows in (4 * SEG_PER_BLOCK, 2 * SEG_PER_BLOCK):
        p = fft_api.plan(kind="c2c", n=FFT_LEN, batch_shape=(rows,),
                         impl="ref")
        assert p.trace_counts["forward"] == 1, (rows, p.trace_counts)


# ---------------------------------------------------------------------------
# fault tolerance


class _FlakyEncodeTransform(SegmentFFTTransform):
    """Writeback-stage fault injection: encode of one block fails once."""

    def __init__(self, *a, fail_index: int, **kw):
        super().__init__(*a, **kw)
        self.fail_index = fail_index
        self.fails = 0

    def encode(self, host, row0, d):
        if d.index == self.fail_index and self.fails < 1:
            self.fails += 1
            raise RuntimeError("injected writeback failure")
        return super().encode(host, row0, d)


class _FlakyDecodeTransform(SegmentFFTTransform):
    """Read-stage fault injection: decode of one block fails twice."""

    def __init__(self, *a, fail_index: int, **kw):
        super().__init__(*a, **kw)
        self.fail_index = fail_index
        self.fails = 0

    def decode(self, data, index):
        if index == self.fail_index and self.fails < 2:
            self.fails += 1
            raise RuntimeError("injected decode failure")
        return super().decode(data, index)


def test_midstream_writeback_failure_retries(tmp_path):
    store = _signal_store(tmp_path, blocks=6)
    expect = _run_serial(store, tmp_path)
    tr = _FlakyEncodeTransform(FFT_LEN, impl="ref", fail_index=3)
    job = MapOnlyJob(store, tmp_path / "out", transform=tr,
                     config=JobConfig(coalesce=4, inflight=2, max_retries=3,
                                      speculation=False),
                     pipelined=True)
    stats = job.run()
    job.merge(tmp_path / "m.bin")
    assert (tmp_path / "m.bin").read_bytes() == expect
    assert tr.fails == 1
    assert stats.retries == 1
    assert stats.blocks_done == 6


def test_midstream_decode_failure_retries(tmp_path):
    store = _signal_store(tmp_path, blocks=6)
    expect = _run_serial(store, tmp_path)
    tr = _FlakyDecodeTransform(FFT_LEN, impl="ref", fail_index=1)
    job = MapOnlyJob(store, tmp_path / "out", transform=tr,
                     config=JobConfig(coalesce=3, max_retries=5,
                                      speculation=False),
                     pipelined=True)
    stats = job.run()
    job.merge(tmp_path / "m.bin")
    assert (tmp_path / "m.bin").read_bytes() == expect
    assert stats.retries == 2


def test_realize_failure_releases_staging_and_retries(tmp_path):
    """Device errors surface at realize (async dispatch); each transient
    failure must return its staging set to the pool or the dispatcher
    starves after capacity leaks (inflight+2 sets)."""
    store = _signal_store(tmp_path, blocks=8)
    expect = _run_serial(store, tmp_path)

    class Boom:
        def __array__(self, *a, **k):
            raise RuntimeError("injected realize failure")

    class FlakyRealize(SegmentFFTTransform):
        fails = 0

        def realize(self, handle):
            if self.fails < 5:  # > pool capacity for inflight=1
                self.fails += 1
                (_, _), batch = handle
                # np.asarray raises INSIDE the base realize: the finally
                # there must still return `batch` to the pool
                return super().realize(((Boom(), Boom()), batch))
            return super().realize(handle)

    tr = FlakyRealize(FFT_LEN, impl="ref")
    job = MapOnlyJob(store, tmp_path / "out", transform=tr,
                     config=JobConfig(coalesce=2, inflight=1, max_retries=9,
                                      speculation=False),
                     pipelined=True)
    stats = job.run()
    job.merge(tmp_path / "m.bin")
    assert (tmp_path / "m.bin").read_bytes() == expect
    assert tr.fails == 5
    assert stats.blocks_done == 8


def test_launch_failure_discards_batch_and_retries(tmp_path):
    """A launch that dies after gather must discard the gathered staging
    (it has no realize to release it) — repeated failures would otherwise
    deadlock the pool."""
    store = _signal_store(tmp_path, blocks=8)
    expect = _run_serial(store, tmp_path)

    class FlakyLaunch(SegmentFFTTransform):
        fails = 0

        def launch(self, batch):
            if self.fails < 5:  # > pool capacity for inflight=1
                self.fails += 1
                raise RuntimeError("injected launch failure")
            return super().launch(batch)

    tr = FlakyLaunch(FFT_LEN, impl="ref")
    job = MapOnlyJob(store, tmp_path / "out", transform=tr,
                     config=JobConfig(coalesce=2, inflight=1, max_retries=9,
                                      speculation=False),
                     pipelined=True)
    stats = job.run()
    job.merge(tmp_path / "m.bin")
    assert (tmp_path / "m.bin").read_bytes() == expect
    assert tr.fails == 5
    assert stats.blocks_done == 8


def test_stream_poisoned_block_fails_job(tmp_path):
    store = _signal_store(tmp_path, blocks=4)
    tr = _FlakyDecodeTransform(FFT_LEN, impl="ref", fail_index=2)
    tr.fails = -10**9  # never stops failing
    job = MapOnlyJob(store, tmp_path / "out", transform=tr,
                     config=JobConfig(coalesce=2, max_retries=3),
                     pipelined=True)
    with pytest.raises(RuntimeError, match="block 2 failed 3 times"):
        job.run()
    assert job.manifest.tasks[2].status == "FAILED"


def test_stream_resume_skips_done_blocks(tmp_path):
    store = _signal_store(tmp_path, blocks=6)
    kwargs = dict(transform=SegmentFFTTransform(FFT_LEN, impl="ref"),
                  config=JobConfig(coalesce=4), pipelined=True)
    MapOnlyJob(store, tmp_path / "out", **kwargs).run()
    stats = MapOnlyJob(store, tmp_path / "out", **kwargs).run()
    assert stats.attempts == 0  # manifest remembers DONE across restarts


def test_stream_speculation_fires(tmp_path):
    store = _signal_store(tmp_path, blocks=8)

    class SlowTail(SegmentFFTTransform):
        def encode(self, host, row0, d):
            time.sleep(0.8 if d.index == 7 else 0.005)
            return super().encode(host, row0, d)

    job = MapOnlyJob(store, tmp_path / "out",
                     transform=SlowTail(FFT_LEN, impl="ref"),
                     config=JobConfig(coalesce=1, inflight=4, writers=3,
                                      straggler_factor=2.0,
                                      min_completed_for_speculation=3),
                     pipelined=True)
    stats = job.run()
    assert stats.blocks_done == 8
    assert stats.speculative_launches >= 1


def test_mapfn_straggler_rescued_by_speculation(tmp_path):
    """A hung map_fn must not block the dispatcher: launch goes through
    the MapFnTransform compute pool, so a speculative twin completes the
    block and the job finishes while the primary is still stuck."""
    store = _signal_store(tmp_path, blocks=8)
    release = threading.Event()
    seen: list[int] = []

    def hang_once(data, idx):
        seen.append(idx)
        if idx == 5 and seen.count(5) == 1:
            release.wait(timeout=30)  # primary attempt hangs
        return data

    job = MapOnlyJob(store, tmp_path / "out", hang_once,
                     JobConfig(straggler_factor=2.0,
                               min_completed_for_speculation=3,
                               poll_interval_s=0.01),
                     pipelined=True)
    stats = job.run()
    release.set()  # unblock the abandoned primary thread
    assert stats.blocks_done == 8
    assert stats.speculative_launches >= 1
    job.merge(tmp_path / "m.bin")  # every block's output landed


# ---------------------------------------------------------------------------
# staging pool back-pressure


def test_staging_pool_bounds_and_reuse():
    stop = threading.Event()
    pool = StagingPool(capacity=1, stop=stop)
    a = pool.acquire((4, 8))
    got = []

    def second():
        got.append(pool.acquire((4, 8)))

    t = threading.Thread(target=second)
    t.start()
    t.join(timeout=0.2)
    assert t.is_alive()  # capacity 1 -> second acquire blocks
    pool.release((4, 8), a)
    t.join(timeout=2.0)
    assert not t.is_alive()
    assert got and got[0][0] is a[0]  # the SAME buffer was recycled


# ---------------------------------------------------------------------------
# manifest journal (append-only + compaction + crash replay)


def test_manifest_journal_is_o1_per_transition(tmp_path):
    m = Manifest(tmp_path / "j.json", num_blocks=64)
    base = (tmp_path / "j.json").stat().st_size
    m.update(0, status="RUNNING")
    one = (tmp_path / "j.json").stat().st_size - base
    for i in range(1, 33):
        m.update(i, status="RUNNING")
    grown = (tmp_path / "j.json").stat().st_size - base
    # append-only: each transition costs ~one line, NOT a table rewrite
    assert one < 128
    assert grown <= 33 * one + 64
    assert m.appends == 33


def test_manifest_crash_replay(tmp_path):
    path = tmp_path / "j.json"
    m = Manifest(path, num_blocks=4)
    m.update(0, status="DONE", finished_at=1.0)
    m.update(1, status="RUNNING", started_at=2.0)
    m.update(2, status="FAILED", attempts=3, error="boom")
    # crash: no compaction, journal is snapshot + 3 update lines
    assert len(path.read_text().splitlines()) == 4

    m2 = Manifest(path, num_blocks=4)
    assert m2.tasks[0].status == "DONE"
    assert m2.tasks[1].status == "PENDING"  # RUNNING at crash -> retry
    assert m2.tasks[2].status == "FAILED"
    assert m2.tasks[2].error == "boom"
    assert m2.tasks[3].status == "PENDING"
    # compaction on open: back to a single snapshot line
    assert len(path.read_text().splitlines()) == 1


def test_manifest_tolerates_torn_tail_write(tmp_path):
    path = tmp_path / "j.json"
    m = Manifest(path, num_blocks=3)
    m.update(0, status="DONE")
    with open(path, "a") as f:  # crash mid-append: half a JSON line
        f.write('{"type": "update", "index": 2, "fie')
    m2 = Manifest(path, num_blocks=3)
    assert m2.tasks[0].status == "DONE"  # durable prefix survives
    assert m2.tasks[2].status == "PENDING"  # torn record dropped


def test_manifest_reads_legacy_format(tmp_path):
    path = tmp_path / "j.json"
    legacy = {str(i): vars(TaskState(i)) for i in range(3)}
    legacy["1"]["status"] = "DONE"
    path.write_text(json.dumps(legacy))
    m = Manifest(path, num_blocks=3)
    assert m.tasks[1].status == "DONE"
    assert m.tasks[0].status == "PENDING"


def test_manifest_crash_mid_compact_replays_same_states(
        tmp_path, monkeypatch):
    """A crash inside _compact (power cut between tmp-write and rename)
    must leave the journal byte-identical, so a reopen replays the SAME
    task states — and must not leak the tmp snapshot file."""
    import os as _os

    path = tmp_path / "j.json"
    m = Manifest(path, num_blocks=4)
    m.update(0, status="DONE", finished_at=1.0)
    m.update(1, status="RUNNING", started_at=2.0)
    m.update(3, status="FAILED", attempts=3, error="boom")
    m.close()
    with open(path, "a") as f:  # plus a torn tail from the same crash
        f.write('{"type": "update", "index": 2, "fie')
    journal_before = path.read_bytes()

    real_replace = _os.replace

    def crash_replace(src, dst):
        raise OSError("simulated crash mid-compact")

    monkeypatch.setattr("repro.core.pipeline.maponly.os.replace",
                        crash_replace)
    with pytest.raises(OSError, match="mid-compact"):
        Manifest(path, num_blocks=4)
    monkeypatch.setattr("repro.core.pipeline.maponly.os.replace",
                        real_replace)

    # the journal is untouched and no .mtmp_ snapshot leaked
    assert path.read_bytes() == journal_before
    assert not list(tmp_path.glob(".mtmp_*"))

    m2 = Manifest(path, num_blocks=4)
    assert m2.tasks[0].status == "DONE"
    assert m2.tasks[1].status == "PENDING"  # RUNNING at crash -> retry
    assert m2.tasks[2].status == "PENDING"  # torn record dropped
    assert m2.tasks[3].status == "FAILED"
    assert m2.tasks[3].error == "boom"
    # and the successful reopen compacted back to one snapshot line
    assert len(path.read_text().splitlines()) == 1
    m2.update(2, status="DONE")  # journal usable after recovery
    assert Manifest(path, num_blocks=4).tasks[2].status == "DONE"
