"""CPU runs of the one-chip cells at a small size, the staged file cell
among them: the result line's shape, the control that must fail, and
faults planted under the timed path that must make ``correct`` false.
Nothing here looks for a chip."""

import json

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


def _run(cell, **kw):
    cfg, tr = _small(cell.rsplit(".", 1)[1])
    line, checks = run.run_cell(discover.with_staged(
        discover.load_benchmark()), cell,
                                2**40 + 17, 0.3, False, config=cfg,
                                traffic=tr, **kw)
    json.dumps(line)
    return line


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
