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
configuration's job settings. Set-up ends at the first block written
once the read-ahead has filled (``Gate``): the stream's decoded queue at
its cap (what the ``queued`` of ``fft.stream.dispatch`` reports) and a
block in each reader's hand, where the device or the writers set the
pace; or, where the reads set it, the dispatcher waiting on the decoded
queue between two writes. The job is then as it is in its steady
state. The window opens there and lasts ``seconds``. Its work is the
points of the blocks written, each block counted by the share of its
write (encode, write, fsync) that fell inside the window, so a block
whose write straddles an edge counts in part and the rate does not
step by whole blocks. Then the
job is stopped: launches, reads and writes that start after the close
raise ``WindowClosed``, which no retry takes up; writes under way
finish. A trace names what the host did in each idle gap by the
program's ``fft.stream.*`` spans.

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


#: seconds for the job to stop once the window has closed (twice that
#: for the window to open) before the run counts the job as failed
STALL_S = 60.0

#: seconds the dispatcher waits on the decoded queue between two writes
#: that show the reads set the pace: the read-ahead is then as full as
#: it gets
READS_PACE_S = 0.1


class WindowClosed(BaseException):
    """The window has closed: the job stops. A ``BaseException``, so the
    job's retry policy (which retries ``Exception``) ends the job at once
    rather than retrying the block."""


def readahead_blocks(job: dict) -> int:
    """Blocks decoded and not yet gathered once the stream's read-ahead
    is full: its decoded queue's bound in ``StreamExecutor``
    (``core/pipeline/stream.py``), 2 x coalesce + readers, and a block
    in each reader's hand, waiting to be put."""
    coalesce, readers = max(job["coalesce"], 1), max(job["readers"], 1)
    return (2 * coalesce + readers) + readers


class Gate:
    """What the job did when, as the benchmark sees it: blocks decoded,
    gathered, read and written (each write's start and end, and its
    writer thread); whether the window has opened and whether it has
    closed.

    The read-ahead's depth is the blocks decoded that are neither in a
    group gathered so far nor written. It is full once the depth has
    reached ``full`` (``by`` "cap"), or once the dispatcher has waited
    ``READS_PACE_S`` or more on the decoded queue between two writes
    (``by`` "reads"; ``waits`` is the job's ``JobStats.wait_s``). The
    window opens at the first block written once it is full."""

    def __init__(self, full: int):
        self.lock = threading.Lock()
        self.full = full
        self.waits = {}       # the dispatcher's waits, JobStats.wait_s
        self.waited = None    # its wait on decoded blocks at the last write
        self.written = []     # (start, end, block index, writer thread)
        self.encoding = {}    # writer thread -> when its encode began
        self.reads = []                   # (monotonic time, block index)
        self.decoded = set()  # blocks decoded and not yet gathered
        self.done = set()     # blocks written
        self.depths = []      # (monotonic time, depth) at every decode
        self.started = None   # the job's start
        self.filled = None    # when the read-ahead was first full
        self.by = None        # what showed it full: "cap" or "reads"
        self.opened = threading.Event()   # the window has opened
        self.closed = threading.Event()   # the window has closed

    def check_open(self) -> None:
        if self.closed.is_set():
            raise WindowClosed

    def decode(self, index: int) -> None:
        now = time.monotonic()
        with self.lock:
            self.decoded.add(index)
            depth = len(self.decoded - self.done)
            self.depths.append((now, depth))
            if self.filled is None and depth >= self.full:
                self.filled, self.by = now, "cap"

    def gather(self, indices) -> None:
        with self.lock:
            self.decoded.difference_update(indices)

    def encode(self) -> None:
        with self.lock:
            self.encoding[threading.get_ident()] = time.monotonic()

    def wrote(self, index: int, start: float) -> None:
        """A block written by this thread: its write began at its encode,
        where the thread encoded it, else at ``start``."""
        now, writer = time.monotonic(), threading.get_ident()
        with self.lock:
            start = self.encoding.pop(writer, start)
            self.written.append((start, now, index, writer))
            self.done.add(index)
            waited = self.waits.get("decoded", 0.0)
            if self.filled is None and self.waited is not None and (
                    waited - self.waited >= READS_PACE_S):
                self.filled, self.by = now, "reads"
            self.waited = waited
            if self.filled is not None:
                self.opened.set()

    def read(self, index: int) -> None:
        with self.lock:
            self.reads.append((time.monotonic(), index))


def window_share(start: float, end: float, lo: float, hi: float) -> float:
    """The share of the interval ``[start, end]`` inside ``[lo, hi]``."""
    inside = min(end, hi) - max(start, lo)
    return max(inside, 0.0) / (end - start) if end > start else float(
        lo <= start <= hi)


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
            try:
                data = part.read_block(0, verify)
            finally:
                self.evict(part)
            self.gate.read(index)
            return data

        def write_output_block(self, out_dir, index: int, data) -> None:
            self.gate.check_open()
            start = time.monotonic()
            super().write_output_block(out_dir, index, data)
            self.gate.wrote(index, start)

    return CaptureStore, BlockStore


class _GatedTransform:
    """The job's transform, telling the gate what was decoded, gathered
    and encoded, and launching nothing once the window has closed."""

    def __init__(self, inner, gate: Gate):
        self._inner = inner
        self._gate = gate

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def decode(self, data, index):
        d = self._inner.decode(data, index)
        self._gate.decode(index)
        return d

    def gather(self, group):
        self._gate.gather([d.index for d in group])
        return self._inner.gather(group)

    def launch(self, batch):
        self._gate.check_open()
        return self._inner.launch(batch)

    def encode(self, host, row0, d):
        self._gate.encode()
        return self._inner.encode(host, row0, d)


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
    gate = Gate(readahead_blocks(jc))
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
    gate.waits = job.stats.wait_s
    ended = {}

    def job_thread():
        try:
            ended["stats"] = job.run()
        except RuntimeError as e:
            ended["error"] = e
        finally:
            gate.opened.set()
            gate.closed.set()

    harness.steady()
    worker = threading.Thread(target=job_thread, name="bench.job",
                              daemon=True)
    gate.started = time.monotonic()
    worker.start()
    # the read-ahead fills while the first run of a checkout compiles
    opened = gate.opened.wait(2 * STALL_S)

    with harness.Window(cell.trace, cell.tmp, counter) as win:
        with gate.lock:
            stage0 = dict(job.stats.stage_s)
            retries0 = job.stats.retries
        # the job ends before the close only if it failed or ran out
        ended_early = not opened or gate.closed.wait(cell.seconds)
        with gate.lock:
            stage1 = dict(job.stats.stage_s)
            retries1 = job.stats.retries
    gate.closed.set()
    peak = harness.memory_peak(jax.local_devices()[:cell.chips])
    worker.join(STALL_S)

    err = ended.get("error")
    stopped = err is not None and isinstance(err.__cause__, WindowClosed)
    job_failed = ended_early or worker.is_alive() or (err is not None
                                                      and not stopped)
    first = {}            # each block's first write: a twin's is a repeat
    for s, e, i, w in gate.written:
        first.setdefault(i, (s, e, w))
    share = {i: window_share(s, e, win.start, win.end)
             for i, (s, e, _) in first.items()}
    unique = sorted(i for i, f in share.items() if f > 0)
    done = [i for _, e, i, _ in gate.written if win.start < e <= win.end]
    writers = {w: n for n, w in enumerate(dict.fromkeys(
        w for *_, w in gate.written))}
    reads = [i for t, i in gate.reads if win.start < t <= win.end]

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
                 sum(share.values()) * points_per_block / win.seconds / 1e6},
        attempted=len(unique), failed=(retries1 - retries0) + job_failed,
        checks=[("max_rel_l2", err_l2, cfg["check"]["max_rel_l2"])],
        compiles_in_window=win.compiles, memory_peak_bytes=peak,
        trace_file=win.trace_file,
        layer={"stage_s": _stage_delta(stage1, stage0),
               "bytes_read": len(reads) * block_bytes,
               "bytes_written": len(done) * block_bytes,
               "blocks_in_window": sum(share.values()),
               "window_s": win.seconds,
               "writes": [[s - win.start, e - win.start, writers[w]]
                          for i, (s, e, w) in sorted(first.items())
                          if share[i] > 0],
               "ran_out": "stats" in ended,
               "setup_phases_s": {"capture": t_made - t0,
                                  "store": t_stored - t_made,
                                  "steady": win.start - t_stored},
               "readahead": {
                   "full": gate.full,
                   "by": gate.by,
                   "filled_s": (None if gate.filled is None
                                else gate.filled - gate.started),
                   "depths": [[round(t - gate.started, 3), d]
                              for t, d in gate.depths if t <= win.start]},
               "blocks_written_run": len(gate.written),
               "speculative": stats.speculative_launches,
               "batches": stats.batches})
