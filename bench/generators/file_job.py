"""The paper's file job: a capture on disk to spectra on disk, streamed.

Set-up makes ``distinct_blocks`` blocks of the capture (interleaved
complex64 segments) from the seed on the device and writes them at once,
each to a one-block ``BlockStore`` in the run's scratch directory outside
the checkout (every block fsynced by the store). The job's store lists
``capture_blocks`` blocks whose content repeats every
``distinct_blocks``: block ``i`` is read from the store of block
``i mod distinct_blocks``, so a capture of many blocks costs the disk
only its distinct ones. Every read drops the file's pages from the page
cache once the block is in memory, so each read is a read of the disk,
as for a capture larger than memory.

One ``MapOnlyJob(pipelined=True)`` then runs over the capture with the
configuration's job settings. Set-up ends when its first output block is
written: the job's program is loaded and its stages are full. The window
opens there and closes at the first block that the same writer thread
writes once ``seconds`` have passed (``Gate``), so it begins and ends on
a written block and holds whole periods of the writers.
The rate is the points of the blocks written inside the window over its
length. Then the job is stopped: launches, reads and writes after the
close raise ``WindowClosed``, which no retry takes up. The host spans
``bench.read``/``gather``/``launch``/``realize``/``write`` mark the
stages' calls, so a trace names what the host did in each idle gap.

Checked: rows drawn from the seed of every block written in the window,
read back from disk, against the configuration's float64 reference of
the same capture rows.

Traffic parameter: ``sample_rows`` (rows checked per output block).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import check, harness


#: seconds past the window's end without a block written, or for the
#: job to stop, before the run counts the job as failed
STALL_S = 60.0


class WindowClosed(BaseException):
    """The window has closed: the job stops. A ``BaseException``, so the
    job's retry policy (which retries ``Exception``) ends the job at once
    rather than retrying the block."""


class Gate:
    """The window's clock as the job's writers see it: which blocks were
    written when and by which writer thread, and whether the window has
    closed.

    The window closes at the first block written once ``deadline`` has
    passed by the writer that wrote the block opening it. Writers finish
    blocks at one rate but at different phases, so a window that began
    with one writer's block and ended with another's would hold half a
    block's time more or less than its blocks; ending on the same
    writer's block holds whole periods of both."""

    def __init__(self):
        self.lock = threading.Lock()
        self.written = []     # (monotonic time, block index, writer thread)
        self.reads = []                   # (monotonic time, block index)
        self.wrote_one = threading.Event()  # set at every block written
        self.closed = threading.Event()   # the window has closed
        self.deadline = None
        self.anchor = None    # the writer thread whose block opened it

    def check_open(self) -> None:
        if self.closed.is_set():
            raise WindowClosed

    def wrote(self, index: int) -> None:
        now, writer = time.monotonic(), threading.get_ident()
        with self.lock:
            self.written.append((now, index, writer))
            self.wrote_one.set()
            if (self.deadline is not None and now >= self.deadline
                    and writer == self.anchor):
                self.closed.set()

    def read(self, index: int) -> None:
        with self.lock:
            self.reads.append((time.monotonic(), index))


def _store_class():
    from repro.core.pipeline import BlockStore

    class CaptureStore(BlockStore):
        """The capture as the job sees it: ``blocks`` blocks, block ``i``
        read from the one-block store ``parts[i mod len(parts)]`` and
        dropped from the page cache once read; reads, writes and the
        window's clock go through the gate."""

        def loop(self, parts: list, blocks: int, gate: Gate) -> None:
            from dataclasses import replace
            self.parts, self.gate = parts, gate
            self.blocks = [replace(parts[i % len(parts)].blocks[0], index=i,
                                   offset=i * self.block_bytes)
                           for i in range(blocks)]
            self.total_bytes = blocks * self.block_bytes

        def evict(self, part) -> None:
            for r in range(part.replication):
                harness.evict(part.root / part.blocks[0].name(r))

        def read_block(self, index: int, verify: bool = True) -> bytes:
            self.gate.check_open()
            part = self.parts[index % len(self.parts)]
            with _span("bench.read"):
                try:
                    data = part.read_block(0, verify)
                finally:
                    self.evict(part)
            self.gate.read(index)
            return data

        def write_output_block(self, out_dir, index: int, data) -> None:
            self.gate.check_open()
            with _span("bench.write"):
                super().write_output_block(out_dir, index, data)
            self.gate.wrote(index)

    return CaptureStore, BlockStore


class _GatedTransform:
    """The job's transform, launching nothing once the window has closed."""

    def __init__(self, inner, gate: Gate):
        self._inner = inner
        self._gate = gate

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def gather(self, group):
        with _span("bench.gather"):
            return self._inner.gather(group)

    def launch(self, batch):
        self._gate.check_open()
        with _span("bench.launch"):
            return self._inner.launch(batch)

    def realize(self, handle):
        with _span("bench.realize"):
            return self._inner.realize(handle)


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _capture(cell: harness.Cell) -> np.ndarray:
    """The distinct blocks, (segments, fft_len, 2) float32, made on the
    device."""
    import jax
    import jax.numpy as jnp
    cfg = cell.config
    shape = (int(cfg["distinct_blocks"]) * cfg["segments_per_block"],
             cfg["fft_len"], 2)
    make = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))
    return np.asarray(make(harness.jax_key(cell.seed)))


def _sample(rng, seg: int, k: int) -> np.ndarray:
    return np.sort(rng.choice(seg, k, replace=False))


def control(cell: harness.Cell) -> list:
    """The check with the bf16 x3 DFT (``Precision.HIGH``) in the
    program's place, over rows of each distinct block."""
    import jax.numpy as jnp
    seg = cell.config["segments_per_block"]
    capture = _capture(cell)
    rng = harness.numpy_rng(cell.seed, 2)
    err = 0.0
    for b in range(len(capture) // seg):
        x = capture[b * seg:(b + 1) * seg]
        yr, yi = check.control_dft(jnp.asarray(x[..., 0]),
                                   jnp.asarray(x[..., 1]))
        r = _sample(rng, seg, int(cell.traffic["sample_rows"]))
        want = cell.reference.spectra(x[r, :, 0], x[r, :, 1])
        err = max(err, check.rel_l2(np.asarray(yr)[r] + 1j
                                    * np.asarray(yi)[r], want))
    return [("max_rel_l2", err, cell.config["check"]["max_rel_l2"])]


def _stage_delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def run(cell: harness.Cell, counter: harness.CompileCounter,
        transform=None) -> harness.Outcome:
    """``transform`` replaces the job's ``SegmentFFTTransform`` (tests)."""
    import jax
    from repro.core.pipeline import JobConfig, MapOnlyJob, SegmentFFTTransform
    from repro.core.pipeline.records import segment_block_bytes

    cfg, tr = cell.config, cell.traffic
    n, seg, jc = cfg["fft_len"], cfg["segments_per_block"], cfg["job"]
    block_bytes = segment_block_bytes(n, seg)
    blocks = int(cfg["capture_blocks"])
    points_per_block = seg * n

    t0 = time.monotonic()
    capture = _capture(cell)
    t_made = time.monotonic()
    distinct = int(cfg["distinct_blocks"])
    gate = Gate()
    capture_store, block_store = _store_class()
    parts = [block_store(cell.tmp / f"capture{b}", block_bytes=block_bytes,
                         replication=jc["replication"])
             for b in range(distinct)]
    # the distinct blocks written at once, as to the datanodes of a store
    with ThreadPoolExecutor(distinct) as pool:
        list(pool.map(lambda b: parts[b].put_array(
            capture[b * seg:(b + 1) * seg]), range(distinct)))
    store = capture_store(cell.tmp / "capture", block_bytes=block_bytes,
                          replication=jc["replication"])
    store.loop(parts, blocks, gate)
    for part in parts:
        store.evict(part)
    t_stored = time.monotonic()
    job = MapOnlyJob(
        store, cell.tmp / "out",
        config=JobConfig(workers=jc["workers"], readers=jc["readers"],
                         writers=jc["writers"], coalesce=jc["coalesce"],
                         inflight=jc["inflight"],
                         max_retries=jc["max_retries"],
                         speculation=jc["speculation"]),
        pipelined=True,
        transform=_GatedTransform(
            transform or SegmentFFTTransform(n, impl=cfg["impl"]), gate))
    ended = {}

    def job_thread():
        try:
            ended["stats"] = job.run()
        except RuntimeError as e:
            ended["error"] = e
        finally:
            gate.wrote_one.set()
            gate.closed.set()

    harness.steady()
    worker = threading.Thread(target=job_thread, name="bench.job",
                              daemon=True)
    worker.start()
    gate.wrote_one.wait()

    with harness.Window(cell.trace, cell.tmp, counter) as win:
        with gate.lock:
            gate.anchor = gate.written[0][2]
            gate.deadline = win.start + cell.seconds
            stage0 = dict(job.stats.stage_s)
            retries0 = job.stats.retries
        with _span("bench.stream"):
            stalled = not gate.closed.wait(cell.seconds + STALL_S)
            gate.closed.set()
        with gate.lock:
            stage1 = dict(job.stats.stage_s)
            retries1 = job.stats.retries
    peak = harness.memory_peak(jax.local_devices()[:cell.chips])
    worker.join(STALL_S)

    err = ended.get("error")
    stopped = err is not None and isinstance(err.__cause__, WindowClosed)
    job_failed = stalled or worker.is_alive() or (err is not None
                                                  and not stopped)
    inside = [(t, i, w) for t, i, w in gate.written
              if win.start < t <= win.end]
    done = [i for _, i, _ in inside]
    writers = {w: n for n, w in enumerate(dict.fromkeys(
        w for _, _, w in gate.written))}
    reads = [i for t, i in gate.reads if win.start < t <= win.end]
    unique = sorted(set(done))

    rng = harness.numpy_rng(cell.seed, 2)
    out_dir = cell.tmp / "out"
    err_l2 = float("inf") if job_failed or not unique else 0.0
    for b in unique:
        r = _sample(rng, seg, int(tr["sample_rows"]))
        path = out_dir / store.blocks[b].name()
        got = np.memmap(path, np.float32, "r").reshape(seg, n, 2)[r]
        x = capture[(b % distinct) * seg + r]
        want = cell.reference.spectra(x[..., 0], x[..., 1])
        err_l2 = max(err_l2, check.rel_l2(got[..., 0] + 1j * got[..., 1],
                                          want))
    stats = job.stats
    return harness.Outcome(
        window_start=win.start,
        metrics={"mpoints_per_s.file":
                 len(unique) * points_per_block / win.seconds / 1e6},
        attempted=len(unique), failed=(retries1 - retries0) + job_failed,
        checks=[("max_rel_l2", err_l2, cfg["check"]["max_rel_l2"])],
        compiles_in_window=win.compiles, memory_peak_bytes=peak,
        trace_file=win.trace_file,
        layer={"stage_s": _stage_delta(stage1, stage0),
               "bytes_read": len(reads) * block_bytes,
               "bytes_written": len(done) * block_bytes,
               "blocks_written": len(unique), "window_s": win.seconds,
               "writes": [[t - win.start, writers[w]] for t, _, w in inside],
               "ran_out": "stats" in ended,
               "setup_phases_s": {"capture": t_made - t0,
                                  "store": t_stored - t_made,
                                  "first_block": win.start - t_stored},
               "speculative": stats.speculative_launches,
               "batches": stats.batches})
