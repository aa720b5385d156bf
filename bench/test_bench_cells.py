"""CPU runs of the one-chip cells at a small size, the resident cell of
``BENCHMARK.json`` and the file cell staged beside it
(``discover.with_staged``): the result line's shape, the file cell's
window opening on a full read-ahead, the control that must fail, and
faults planted under the timed path that must make ``correct`` false.
Nothing here looks for a chip."""

import json
import time

import numpy as np
import pytest

from bench import check, discover, run

RESIDENT = "paper_capture_c2c1024.resident"
FILE = "paper_capture_c2c1024.file"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _small(which):
    cfg = discover.load_config("paper_capture_c2c1024")
    cfg.update(fft_len=256, segments_per_block=16)
    cfg["transform"] = {"kind": "c2c", "shape": [256], "batch": 64}
    cfg["job"] = dict(cfg["job"], coalesce=2)
    cfg.update(capture_blocks=256, distinct_blocks=2)
    tr = discover.load_traffic(which)
    tr.update(sample_rows=4 if which == "file" else 8)
    return cfg, tr


def _run(cell, seconds=0.3, **kw):
    cfg, tr = _small(cell.rsplit(".", 1)[1])
    line, checks = run.run_cell(discover.with_staged(
        discover.load_benchmark()), cell,
                                2**40 + 17, seconds, False, config=cfg,
                                traffic=tr, **kw)
    json.dumps(line)
    return line


def test_file_window_opens_once_the_readahead_is_full(capsys):
    """A run of the file cell: the window opened once the decoded queue
    held its cap (2 x coalesce + readers) and each reader a block more,
    and its rate counts every block written in it by its share."""
    line = _run(FILE)
    layer = next(json.loads(s)["layer"] for s in
                 capsys.readouterr().out.splitlines()
                 if s.startswith('{"compiles_in_window"'))
    ahead = layer["readahead"]
    assert line["correct"] is True
    assert ahead["full"] == (2 * 2 + 2) + 2   # coalesce 2, readers 2
    assert ahead["by"] == "cap"
    assert max(d for _, d in ahead["depths"]) >= ahead["full"]
    assert ahead["filled_s"] is not None
    shares = [file_job().window_share(s, e, 0.0, layer["window_s"])
              for s, e, _ in layer["writes"]]
    assert all(0 < f <= 1 for f in shares)
    assert sum(shares) == pytest.approx(layer["blocks_in_window"])


def file_job():
    return discover.load_generator("file_job")


def _gate(full=8):
    gate = file_job().Gate(full)
    gate.started = time.monotonic()
    return gate


def test_file_readahead_is_the_streams_own_bound():
    """``readahead_blocks`` is the stream's decoded-queue bound and a
    block in each reader's hand, so a change of that bound in the program
    fails here rather than leaving the gate waiting for a depth the
    queue never reaches."""
    from repro.core.pipeline import JobConfig, JobStats, StreamExecutor

    jc = discover.load_config("paper_capture_c2c1024")["job"]
    for job in (jc, dict(jc, coalesce=2), dict(jc, readers=3)):
        cfg = JobConfig(coalesce=job["coalesce"], readers=job["readers"])
        ex = StreamExecutor(None, None, None, cfg, None, JobStats())
        assert file_job().readahead_blocks(job) == (
            ex._decoded.maxsize + cfg.readers)


@pytest.mark.parametrize("ahead,opens", [(7, False), (8, True), (9, True)])
def test_file_window_does_not_open_before_the_readahead_cap(ahead, opens):
    """Blocks written while the readers are still filling the read-ahead
    do not open the window; the first one written once it is full
    does."""
    gate = _gate()
    for i in range(4):
        gate.decode(i)
    gate.gather([0, 1])
    gate.wrote(0, time.monotonic())
    assert not gate.opened.is_set()
    gate.gather([2, 3])
    for i in range(4, 4 + ahead):
        gate.decode(i)
    gate.decode(0)                       # a twin of a block written
    assert gate.depths[-1][1] == ahead
    assert gate.opened.is_set() is False
    gate.wrote(1, time.monotonic())
    assert gate.opened.is_set() is opens


def test_file_readahead_counts_full_once_reads_set_the_pace():
    """Where the reads set the pace the queue never fills: the read-ahead
    counts as full once the dispatcher has waited ``READS_PACE_S`` on the
    decoded queue between two writes. Its wait before the first write,
    while the first group is read, does not count."""
    pace = file_job().READS_PACE_S
    gate = _gate(full=100)
    gate.waits = {"decoded": 5 * pace, "inflight": 0.0}
    for i in range(3):
        gate.decode(i)
    gate.wrote(0, time.monotonic())
    gate.waits["decoded"] += pace / 2
    gate.wrote(1, time.monotonic())
    assert not gate.opened.is_set()
    gate.waits["inflight"] += 10 * pace
    gate.wrote(2, time.monotonic())
    assert not gate.opened.is_set()
    gate.waits["decoded"] += 2 * pace
    gate.wrote(3, time.monotonic())
    assert gate.opened.is_set() and gate.by == "reads"


def test_file_window_opens_where_reads_set_the_pace(capsys):
    """A run of the file cell whose readers are slower than the device and
    the writers: the decoded queue stays near empty, and the window opens
    all the same, on the dispatcher's waits, and the run is correct. The
    group's plan is compiled first, so that the readers do not fill the
    queue while the first launch compiles."""
    import repro.fft as fft_api
    from repro.core.pipeline import SegmentFFTTransform

    cfg, _ = _small("file")
    x = np.zeros((cfg["job"]["coalesce"] * cfg["segments_per_block"],
                  cfg["fft_len"]), np.float32)
    fft_api.plan(kind="c2c", n=cfg["fft_len"], batch_shape=x.shape[:1],
                 impl=cfg["impl"]).execute_async(
                     x, x.copy(), donate=True)[0].block_until_ready()

    class SlowRead(SegmentFFTTransform):
        def decode(self, data, index):
            time.sleep(0.3)
            return super().decode(data, index)

    # a group is read every 0.3 s: a window of several holds writes
    line = _run(FILE, seconds=1.5, transform=SlowRead(256, impl="matfft"))
    layer = next(json.loads(s)["layer"] for s in
                 capsys.readouterr().out.splitlines()
                 if s.startswith('{"compiles_in_window"'))
    assert line["correct"] is True
    assert layer["readahead"]["by"] == "reads"
    assert max(d for _, d in layer["readahead"]["depths"]) < (
        layer["readahead"]["full"])


def test_file_block_write_begins_at_its_encode():
    """A block's write, whose share of the window counts, runs from its
    encode to the end of its fsync, so a writer's blocks tile its time
    rather than leaving the encode out between them."""
    gate = _gate()
    gate.encode()
    begun = time.monotonic()
    time.sleep(0.02)
    gate.wrote(0, time.monotonic())
    start, end, *_ = gate.written[-1]
    assert start <= begun and end - start >= 0.02
    late = time.monotonic()
    gate.wrote(1, late)                  # no encode: its own start
    assert gate.written[-1][0] == late


@pytest.mark.parametrize("start,end,share", [
    (1.0, 3.0, 1.0), (-1.0, 1.0, 0.5), (9.0, 13.0, 0.25),
    (-2.0, 12.0, 10.0 / 14.0), (11.0, 12.0, 0.0), (-3.0, 0.0, 0.0)])
def test_file_blocks_count_by_their_writes_share(start, end, share):
    """A block counts in the window's work by the share of its write
    inside the window, so the rate does not step by whole blocks."""
    assert file_job().window_share(start, end, 0.0, 10.0) == pytest.approx(
        share)


@pytest.mark.parametrize("cell,metric", [(RESIDENT, "gpoints_per_s"),
                                         (FILE, "mpoints_per_s.file")])
def test_result_line_shape(cell, metric):
    line = _run(cell)
    assert list(line) == KEYS and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", metric}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    value, limit = (line["checks"]["max_rel_l2"][k]
                    for k in ("value", "limit"))
    assert 0 < value < limit


def _break(fault, yr, yi, xr, xi):
    import jax.numpy as jnp
    if fault == "unchanged":          # the step returns its input
        return xr, xi
    if fault == "half_batch":         # half the rows left out
        half = yr.shape[0] // 2
        return (yr.at[half:].set(0.0), yi.at[half:].set(0.0))
    # one bin of every row altered by a part in 1e4 of the row's scale
    scale = 1e-4 * jnp.sqrt(jnp.sum(yr * yr + yi * yi, axis=-1))
    return yr.at[:, 3].add(scale), yi


FAULTS = ["unchanged", "half_batch", "altered"]


@pytest.mark.parametrize("fault", FAULTS)
def test_resident_fault_is_not_correct(fault):
    import repro.fft as fft_api
    cfg, _ = _small("resident")
    plan = fft_api.plan(kind="c2c", shape=(256,), batch_shape=(64,),
                        impl=cfg["impl"])

    def execute(xr, xi):
        return _break(fault, *plan.execute_async(xr, xi), xr, xi)

    assert _run(RESIDENT, execute=execute)["correct"] is False


@pytest.mark.parametrize("fault", FAULTS)
def test_file_fault_is_not_correct(fault):
    from repro.core.pipeline import SegmentFFTTransform

    class Broken(SegmentFFTTransform):
        def launch(self, batch):
            handle, staged = super().launch(batch)
            x = tuple(np.array(b) for b in staged)
            return _break(fault, *handle, *x), staged

    transform = Broken(256, impl="matfft")
    assert _run(FILE, transform=transform)["correct"] is False


@pytest.mark.parametrize("which", ["resident", "file"])
def test_control_fails_the_limit_and_the_program_passes(which, tmp_path):
    """Each generator's control (the bf16 x3 DFT, ``Precision.HIGH``, in
    the program's place) goes through ``check.verdict`` at the
    configuration's own limit, at the configuration's 1024 points, and
    fails it; the program passes the same limit on the same rows."""
    import repro.fft as fft_api
    from bench import harness
    cfg, tr = _small(which)
    cfg.update(fft_len=1024)
    cfg["transform"] = {"kind": "c2c", "shape": [1024], "batch": 16}
    cell = harness.Cell(name=which, config=cfg, traffic=tr, seed=2**40 + 5,
                        seconds=0.0, trace=False, chips=1,
                        reference=discover.load_reference(
                            "paper_capture_c2c1024"), tmp=tmp_path)
    checks = discover.load_generator(tr["generator"]).control(cell)
    assert [c[2] for c in checks] == [cfg["check"]["max_rel_l2"]]
    assert check.verdict(checks) is False
    rng = np.random.default_rng(5)
    xr, xi = (rng.standard_normal((8, 1024)).astype(np.float32)
              for _ in range(2))
    p = fft_api.plan(kind="c2c", n=1024, batch_shape=(8,), impl="matfft")
    yr, yi = p.execute(xr, xi)
    want = cell.reference.spectra(xr, xi)
    assert check.verdict([("max_rel_l2", check.rel_l2(
        np.asarray(yr) + 1j * np.asarray(yi), want),
        cfg["check"]["max_rel_l2"])]) is True
