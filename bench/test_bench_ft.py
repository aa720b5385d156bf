"""CPU runs of the NPB FT cell of ``BENCHMARK.json`` on four virtual
devices at a small grid: a sound run is correct; the control and faults
planted under the timed path (the pencil) are not. The runs go to a
child process, which sets the CPU device count before JAX starts.

The small grid is held to the configuration's own limit, ``LIMIT``, set
for class D from chip readings (PERF.md): a sound run reads about 3e-7
there and the bf16 x3 control above it.

The grid keeps class D's proportion of NPB's checksum points to x: 32
points over an x of 64, so, as at class D, they all but miss the chips
of the upper half of x, and a fault on one such chip is caught by the
points drawn inside every chip's block."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import check, discover

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT = discover.load_config("npb_ft_classD")["check"]["max_rel_l2"]

CHILD = r"""
import json, sys, tempfile
from pathlib import Path
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from bench import discover, harness, run

cfg = discover.load_config("npb_ft_classD")
cfg.update(shape=[16, 32, 64], alpha=1e-3, checksum_points=32)
traffic = dict(discover.load_traffic("iter"), sample_points=32)
mesh = jax.sharding.Mesh(
    __import__("numpy").array(jax.devices()[:4]).reshape(2, 2),
    ("data", "model"))
in_spec = P(*cfg["in_spec"])


def local_only(vr, vi):
    # each chip transforms the block it holds: the exchanges left out
    def f(r, i):
        y = jnp.fft.fftn(r + 1j * i)
        return y.real.astype(jnp.float32), y.imag.astype(jnp.float32)
    return jax.shard_map(f, mesh=mesh, in_specs=(in_spec, in_spec),
                         out_specs=(in_spec, in_spec))(vr, vi)


def altered(vr, vi):
    import repro.fft as fft_api
    p = fft_api.plan(kind="c2c", shape=tuple(cfg["shape"]), mesh=mesh,
                     placement="distributed", impl=cfg["impl"],
                     overlap=cfg["overlap"])
    yr, yi = p.execute(vr, vi)
    return yr * (1 + 1e-4), yi


def one_chip(vr, vi):
    # the chip at the far corner of the output (data = 1, model = 1)
    # leaves its block unwritten
    import repro.fft as fft_api
    p = fft_api.plan(kind="c2c", shape=tuple(cfg["shape"]), mesh=mesh,
                     placement="distributed", impl=cfg["impl"],
                     overlap=cfg["overlap"])
    yr, yi = p.execute(vr, vi)
    _, ny, nx = cfg["shape"]
    return (yr.at[:, ny // 2:, nx // 2:].set(0.0),
            yi.at[:, ny // 2:, nx // 2:].set(0.0))


faults = {"none": None, "unchanged": lambda vr, vi: (vr, vi),
          "no_exchange": local_only, "altered": altered,
          "one_chip": one_chip}
bench = discover.load_benchmark()
runs = [(name, traffic) for name in faults]
runs.append(("one_chip_npb_only", dict(traffic, points_per_chip=0)))
for name, tr in runs:
    transform = faults[name.replace("_npb_only", "")]
    kw = {} if transform is None else {"transform": transform}
    line, _ = run.run_cell(bench, "npb_ft_classD.iter", 2**35 + 3, 0.3,
                           False, config=cfg, traffic=tr, **kw)
    print(json.dumps({"fault": name, "line": line}), flush=True)
with tempfile.TemporaryDirectory() as tmp:
    cell = harness.Cell(name="npb_ft_classD.iter", config=cfg,
                        traffic=traffic, seed=2**35 + 3, seconds=0.0,
                        trace=False, chips=4,
                        reference=discover.load_reference("npb_ft_classD"),
                        tmp=Path(tmp))
    checks = discover.load_generator("npb_ft").control(cell)
print(json.dumps({"fault": "control", "line": {"checks": checks}}),
      flush=True)
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", CHILD], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    rows = [json.loads(s) for s in out.stdout.splitlines()
            if s.startswith('{"fault"')]
    return {r["fault"]: r["line"] for r in rows}


def test_sound_run_is_correct(runs):
    line = runs["none"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "ft_s_per_iter"}
    assert line["device"]["count"] == 4
    assert line["checks"]["max_rel_l2"]["value"] < LIMIT


def test_control_fails_the_limit_and_the_program_passes(runs):
    """At the test grid the generator's control (the reference in bf16 x3
    products in the program's place) fails the configuration's limit,
    and the program, on the same seed, passes it."""
    (name, value, limit), = runs["control"]["checks"]
    assert name == "max_rel_l2" and limit == LIMIT
    assert check.verdict([(name, value, limit)]) is False
    program = runs["none"]["checks"]["max_rel_l2"]
    assert program["limit"] == LIMIT and program["value"] < LIMIT < value


@pytest.mark.parametrize("fault", ["unchanged", "no_exchange", "altered",
                                   "one_chip"])
def test_fault_is_not_correct(runs, fault):
    assert runs[fault]["correct"] is False


def test_npb_points_alone_miss_a_chip(runs):
    """Without the drawn points the check cannot see the far chip: the
    fault that leaves its block unwritten passes on NPB's points."""
    assert runs["one_chip_npb_only"]["correct"] is True


def test_reference_matches_a_full_transform_and_control_fails():
    """The reference's points against numpy's whole-grid transforms, and
    the bf16 x3 control above ``LIMIT``."""
    ref = discover.load_reference("npb_ft_classD")
    shape, alpha, ts = (16, 32, 64), 1e-3, [1, 25]
    rng = np.random.default_rng(11)
    xr, xi = (rng.standard_normal(shape).astype(np.float32)
              for _ in range(2))
    pts = ref.checksum_points(shape, 64)
    got = ref.points(xr, xi, ts, alpha, pts)
    ctl = ref.control_points(xr, xi, ts, alpha, pts)
    spec = np.fft.fftn(xr.astype(np.float64) + 1j * xi)
    for a, t in enumerate(ts):
        g = 1.0
        for ax, n in enumerate(shape):
            k = ref.signed_index(n).astype(np.float64)
            g = g * np.exp(-4 * np.pi ** 2 * alpha * t * k ** 2).reshape(
                [n if i == ax else 1 for i in range(3)])
        u = np.fft.ifftn(spec * g) * spec.size
        want = u[pts[:, 0], pts[:, 1], pts[:, 2]]
        assert np.linalg.norm(got[a] - want) < 1e-12 * np.linalg.norm(want)
        assert np.linalg.norm(ctl[a] - want) > LIMIT * np.linalg.norm(want)
