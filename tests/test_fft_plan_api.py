"""Plan-and-execute facade invariants (`repro.fft`, DESIGN.md §6).

Covers the tentpole claims:
  * spec resolution validates the whole strategy up front: the auto
    placement heuristic, the distributed `D | n1` constraint as a clear
    plan-time ValueError, and kind/layout/impl/precision membership;
  * the process-level plan cache returns the SAME ExecutablePlan for the
    same resolved spec (different layout/impl miss), and repeat executes
    on identical specs trigger ZERO retraces of the compiled callable;
  * execute / execute_real / execute_inverse match the numpy oracles at
    every placement this host can run;
  * the analytic cost model folds the roofline byte counters.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.fft as fft_api
from repro import compat
from repro.fft.spec import MAX_LOCAL_N, resolve_placement
from repro.kernels.fft import plan as kplan


def _rel_err(got_r, got_i, want):
    got = np.asarray(got_r) + 1j * np.asarray(got_i)
    scale = np.abs(want).max() or 1.0
    return float(np.abs(got - want).max() / scale)


# ---------------------------------------------------------------------------
# placement="auto" heuristic (pure function, unit-tested directly)


def test_auto_local_without_mesh():
    assert resolve_placement(1024, 16, 1, None) == "local"
    assert resolve_placement(MAX_LOCAL_N, 1, 0, None) == "local"


def test_auto_too_large_without_mesh_raises():
    with pytest.raises(ValueError, match="pass mesh"):
        resolve_placement(2 * MAX_LOCAL_N, 1, 0, None)


def test_auto_segmented_for_batches_on_mesh():
    # a 1-D batch of block-sized segments is the paper's map-only regime
    assert resolve_placement(4096, 4096, 1, 8) == "segmented"
    assert resolve_placement(1024, 1024, 1, 512) == "segmented"
    # an indivisible batch cannot shard evenly -> stays local
    assert resolve_placement(1024, 2, 1, 512) == "local"
    assert resolve_placement(256, 3, 1, 8) == "local"


def test_auto_distributed_for_single_large_signal():
    assert resolve_placement(1 << 20, 1, 0, 8) == "distributed"
    assert resolve_placement(1 << 18, 1, 0, 512) == "distributed"


def test_auto_local_when_signal_too_small_to_distribute():
    # n < D^2: the four-step can't split evenly, keep it on one device
    assert resolve_placement(16, 1, 0, 8) == "local"


def test_auto_multidim_batch_stays_local():
    # segmented shards a 1-D (batch, n) layout; framed stft batches stay local
    assert resolve_placement(1024, 64, 2, 8) == "local"


def test_auto_unplaceable_raises():
    # a BATCH of transforms each longer than one device can hold: neither
    # segmented (per-segment cap) nor distributed (needs a scalar batch)
    with pytest.raises(ValueError, match="cannot auto-place"):
        resolve_placement(1 << 30, 4, 1, 8)


# ---------------------------------------------------------------------------
# plan-time validation (clear errors instead of deep shard_map failures)


@pytest.fixture(scope="module")
def mesh():
    return compat.make_mesh((jax.device_count(),), ("data",))


def test_distributed_constraint_valueerror():
    # n < D^2 must name the D | n1 constraint at plan time (spec-level pure
    # check so it runs regardless of this host's device count)
    from repro.fft import spec as spec_mod
    with pytest.raises(ValueError, match=r"D \| n1"):
        spec_mod.resolve(kind="c2c", n=32, batch_shape=(),
                         placement="distributed", layout="zero_copy",
                         impl="matfft", precision="f32", interpret=None,
                         batch_tile=None, num_devices=8, axes=("data",),
                         natural_order=True, fuse_twiddle=False)
    with pytest.raises(ValueError, match="power-of-two device count"):
        spec_mod.resolve(kind="c2c", n=1 << 20, batch_shape=(),
                         placement="distributed", layout="zero_copy",
                         impl="matfft", precision="f32", interpret=None,
                         batch_tile=None, num_devices=6, axes=("data",),
                         natural_order=True, fuse_twiddle=False)


def test_distributed_rejects_r2c(mesh):
    with pytest.raises(ValueError, match="r2c"):
        fft_api.plan(kind="r2c", n=1 << 20, mesh=mesh,
                     placement="distributed")


def test_distributed_rejects_batch(mesh):
    with pytest.raises(ValueError, match="batch"):
        fft_api.plan(kind="c2c", n=1 << 20, batch_shape=(4,), mesh=mesh,
                     placement="distributed")


def test_segmented_requires_mesh_and_1d_batch(mesh):
    with pytest.raises(ValueError, match="mesh"):
        fft_api.plan(kind="c2c", n=512, batch_shape=(8,),
                     placement="segmented")
    with pytest.raises(ValueError, match="1-D batch"):
        fft_api.plan(kind="c2c", n=512, batch_shape=(2, 4), mesh=mesh,
                     placement="segmented")


def test_segmented_indivisible_batch_plan_time_error():
    # explicit segmented with a batch that can't shard evenly must be a
    # plan-time ValueError, not a deep pjit sharding failure at execute
    from repro.fft import spec as spec_mod
    with pytest.raises(ValueError, match="shard evenly"):
        spec_mod.resolve(kind="c2c", n=512, batch_shape=(3,),
                         placement="segmented", layout="zero_copy",
                         impl="matfft", precision="f32", interpret=None,
                         batch_tile=None, num_devices=8, axes=("data",),
                         natural_order=True, fuse_twiddle=False)


def test_bad_enums_raise():
    for kw in (dict(kind="c2r"), dict(layout="strided"), dict(impl="cufft"),
               dict(precision="f64"), dict(placement="cluster")):
        with pytest.raises(ValueError, match="unknown|unsupported"):
            fft_api.plan(**{"kind": "c2c", "n": 256, **kw})


def test_non_pow2_raises():
    with pytest.raises(ValueError, match="power of two"):
        fft_api.plan(kind="c2c", n=768, batch_shape=(2,))


# ---------------------------------------------------------------------------
# plan cache: same spec -> same plan object + compiled fn; no retrace


def test_cache_identity_and_misses():
    fft_api.clear_plan_cache()
    p1 = fft_api.plan(kind="c2c", n=256, batch_shape=(3,))
    p2 = fft_api.plan(kind="c2c", n=256, batch_shape=(3,))
    assert p2 is p1
    assert fft_api.cache_info()["hits"] == 1
    # different layout / impl / kind / batch resolve to different plans
    assert fft_api.plan(kind="c2c", n=256, batch_shape=(3,),
                        layout="copy") is not p1
    assert fft_api.plan(kind="c2c", n=256, batch_shape=(3,),
                        impl="stockham") is not p1
    assert fft_api.plan(kind="r2c", n=256, batch_shape=(3,)) is not p1
    assert fft_api.plan(kind="c2c", n=256, batch_shape=(4,)) is not p1


def test_zero_retrace_on_repeat_execute(rng):
    """The cufftPlanMany property: repeat executes on an identical spec
    reuse the jit'd callable — the traced-fn counter stays at 1 and the
    executable is id-stable."""
    p = fft_api.plan(kind="c2c", n=512, batch_shape=(2,))
    assert p.executable is p.executable
    xr = jnp.asarray(rng.standard_normal((2, 512)).astype(np.float32))
    xi = jnp.asarray(rng.standard_normal((2, 512)).astype(np.float32))
    p.execute(xr, xi)
    assert p.trace_counts["forward"] == 1
    p.execute(xr, xi)
    p.execute(xr + 1.0, xi)  # new values, same shape: still no retrace
    assert p.trace_counts["forward"] == 1
    # the same spec fetched again is the same object -> same compiled fn
    p2 = fft_api.plan(kind="c2c", n=512, batch_shape=(2,))
    p2.execute(xr, xi)
    assert p2 is p and p.trace_counts["forward"] == 1


def test_each_plan_names_its_program():
    """A device trace tells plans apart by their XLA module: the full batch
    from the tail, and the donated and inverse variants, all under the
    ``jit_counted`` prefix the benchmark reads."""
    full = fft_api.plan(kind="c2c", n=256, batch_shape=(8,), impl="ref")
    tail = fft_api.plan(kind="c2c", n=256, batch_shape=(2,), impl="ref")
    x8 = jnp.zeros((8, 256), jnp.float32)
    x2 = jnp.zeros((2, 256), jnp.float32)

    def module(jitted, x):
        first = jitted.lower(x, x).as_text().splitlines()[0]
        return first.split()[1].lstrip("@")

    names = [module(full.executable, x8), module(tail.executable, x2),
             module(full._forward_donated(), x8),
             module(full._inverse()[1], x8)]
    assert names == ["jit_counted_c2c_256_b8_ref", "jit_counted_c2c_256_b2_ref",
                     "jit_counted_c2c_256_b8_ref_donated",
                     "jit_counted_c2c_256_b8_ref_inverse"]


def test_plan_is_frozen():
    p = fft_api.plan(kind="c2c", n=64, batch_shape=(1,))
    with pytest.raises(AttributeError, match="frozen"):
        p.spec = None


# ---------------------------------------------------------------------------
# execution correctness per placement


def test_c2c_local_leaf_and_four_step(rng):
    for n, batch in ((1024, (3,)), (1 << 15, (2,))):
        xr = rng.standard_normal((*batch, n)).astype(np.float32)
        xi = rng.standard_normal((*batch, n)).astype(np.float32)
        p = fft_api.plan(kind="c2c", n=n, batch_shape=batch)
        yr, yi = p.execute(jnp.asarray(xr), jnp.asarray(xi))
        assert _rel_err(yr, yi, np.fft.fft(xr + 1j * xi)) < 5e-6
        br, bi = p.execute_inverse(yr, yi)
        assert float(jnp.abs(br - xr).max()) / np.abs(xr).max() < 1e-5


def test_r2c_execute_real_and_inverse(rng):
    x = rng.standard_normal((3, 2048)).astype(np.float32)
    p = fft_api.plan(kind="r2c", n=2048, batch_shape=(3,))
    sr, si = p.execute_real(jnp.asarray(x))
    assert sr.shape == (3, 1025)
    assert _rel_err(sr, si, np.fft.rfft(x)) < 5e-6
    back = p.execute_inverse(sr, si)
    assert float(jnp.abs(back - x).max()) / np.abs(x).max() < 1e-5


def test_segmented_placement_matches_numpy(mesh, rng):
    xs = rng.standard_normal((8, 512)).astype(np.float32)
    ys = rng.standard_normal((8, 512)).astype(np.float32)
    p = fft_api.plan(kind="c2c", n=512, batch_shape=(8,), mesh=mesh,
                     placement="segmented")
    zr, zi = p.execute(jnp.asarray(xs), jnp.asarray(ys))
    assert _rel_err(zr, zi, np.fft.fft(xs + 1j * ys, axis=-1)) < 5e-6
    p.execute(jnp.asarray(xs), jnp.asarray(ys))
    assert p.trace_counts["forward"] == 1


def test_segmented_r2c_matches_numpy(mesh, rng):
    xs = rng.standard_normal((8, 512)).astype(np.float32)
    p = fft_api.plan(kind="r2c", n=512, batch_shape=(8,), mesh=mesh,
                     placement="segmented")
    sr, si = p.execute_real(jnp.asarray(xs))
    assert _rel_err(sr, si, np.fft.rfft(xs)) < 5e-6


# ---------------------------------------------------------------------------
# guard rails


def test_wrong_method_and_shape_raise(rng):
    pc = fft_api.plan(kind="c2c", n=64, batch_shape=(2,))
    pr = fft_api.plan(kind="r2c", n=64, batch_shape=(2,))
    x = jnp.zeros((2, 64), jnp.float32)
    with pytest.raises(ValueError, match="execute_real"):
        pr.execute(x, x)
    with pytest.raises(ValueError, match="c2c"):
        pc.execute_real(x)
    with pytest.raises(ValueError, match="shape"):
        pc.execute(jnp.zeros((3, 64), jnp.float32),
                   jnp.zeros((3, 64), jnp.float32))
    with pytest.raises(ValueError, match="shape"):
        pr.execute_real(jnp.zeros((2, 128), jnp.float32))


def test_distributed_plan_beyond_single_device_capacity(mesh):
    # global n up to 2^32 is valid for distributed plans: the leaf
    # factorization must cover the per-device pass lengths, not global n
    p = fft_api.plan(kind="c2c", n=1 << 30, mesh=mesh,
                     placement="distributed")
    assert p.dist is not None
    assert max(p.dist.n1, p.dist.n2) == p.leaf.n
    assert p.gemm_macs > 0 and p.collective_bytes > 0


def test_trace_count_ignores_outer_jit_traces(rng):
    # callers jitting over execute (e.g. the deprecated shims inside a
    # user's jax.jit) inline the raw executor; only the plan's own jit
    # traces count toward the zero-retrace observable
    p = fft_api.plan(kind="c2c", n=64, batch_shape=(1,))
    x = jnp.asarray(rng.standard_normal((1, 64)).astype(np.float32))
    jax.jit(lambda a, b: p.execute(a, b))(x, x)
    assert p.trace_counts["forward"] == 0
    p.execute(x, x)
    p.execute(x, x)
    assert p.trace_counts["forward"] == 1


def test_shims_accept_degenerate_lengths(rng):
    # n=1 rfft and 1-bin irfft predate the facade's r2c domain and must
    # keep working through the deprecated shims
    from repro.kernels.fft import ops
    yr, yi = ops.rfft(jnp.ones((2, 1), jnp.float32))
    assert yr.shape == (2, 1)
    out = ops.irfft(jnp.ones((2, 1), jnp.float32),
                    jnp.zeros((2, 1), jnp.float32))
    assert out.shape[0] == 2


def test_overlap_resolution_and_rejection():
    # pure spec-level: runs regardless of this host's device count
    from repro.core.fft.distributed import (
        OVERLAP_AUTO_MIN_N, OVERLAP_RING_MAX_D, plan_distributed,
        resolve_overlap)
    # auto declines small n, huge rings, and 1-wide slabs
    assert resolve_overlap(4096, 8, "auto") is None
    assert resolve_overlap(OVERLAP_AUTO_MIN_N, 8, "auto") == 4
    assert resolve_overlap(OVERLAP_AUTO_MIN_N, 2 * OVERLAP_RING_MAX_D,
                           "auto") is None
    assert resolve_overlap(1 << 30, 8, "off") is None
    # explicit chunk counts are honoured where auto declines, but must
    # divide both per-device slab widths (n=4096, D=8 -> n1l = n2l = 8)
    assert resolve_overlap(4096, 8, 8) == 8
    for bad in (0, -1, 3, 16, "weird", 2.5, True):
        with pytest.raises(ValueError, match="overlap"):
            resolve_overlap(4096, 8, bad)
    # ... and surface through spec resolution as plan-time errors
    from repro.fft import spec as spec_mod
    with pytest.raises(ValueError, match="divide both"):
        spec_mod.resolve(kind="c2c", n=4096, batch_shape=(),
                         placement="distributed", layout="zero_copy",
                         impl="matfft", precision="f32", interpret=None,
                         batch_tile=None, num_devices=8, axes=("data",),
                         natural_order=True, fuse_twiddle=False, overlap=3)
    # "auto" resolves pre-cache-key: the resolved spec never carries it
    s = spec_mod.resolve(kind="c2c", n=4096, batch_shape=(),
                         placement="distributed", layout="zero_copy",
                         impl="matfft", precision="f32", interpret=False,
                         batch_tile=None, num_devices=8, axes=("data",),
                         natural_order=True, fuse_twiddle=False,
                         overlap="auto")
    assert s.overlap == "off"
    # non-distributed placements normalize overlap away entirely
    s2 = spec_mod.resolve(kind="c2c", n=256, batch_shape=(4,),
                          placement="local", layout="zero_copy",
                          impl="matfft", precision="f32", interpret=False,
                          batch_tile=None, num_devices=None, axes=None,
                          natural_order=True, fuse_twiddle=False, overlap=7)
    assert s2.overlap == "off"
    # DistPlan carries the chunk count
    assert plan_distributed(4096, 8, chunks=4).chunks == 4


def test_overlap_cache_key_and_cost_model(mesh):
    n = jax.device_count() ** 2 * 64
    p_off = fft_api.plan(kind="c2c", n=n, mesh=mesh,
                         placement="distributed", overlap="off")
    p_on = fft_api.plan(kind="c2c", n=n, mesh=mesh,
                        placement="distributed", overlap=2)
    assert p_on is not p_off
    assert p_on is fft_api.plan(kind="c2c", n=n, mesh=mesh,
                                placement="distributed", overlap=2)
    # exposed = total / chunks; "off" exposes everything
    assert p_off.exposed_collective_bytes == p_off.collective_bytes
    assert p_off.hidden_collective_bytes == 0
    assert p_on.exposed_collective_bytes * 2 == p_on.collective_bytes
    assert (p_on.hidden_collective_bytes
            == p_on.collective_bytes - p_on.exposed_collective_bytes)
    # overlap does not change the total payload
    assert p_on.collective_bytes == p_off.collective_bytes


def test_collective_bytes_account_for_transposed_out(mesh):
    """The DistPlan fix: natural_order=False skips exchange #3, so both
    the per-device and the plan-level counters report 2 legs, not 3."""
    from repro.core.fft.distributed import plan_distributed
    d_nat = plan_distributed(1 << 20, 8, natural_order=True)
    d_tr = plan_distributed(1 << 20, 8, natural_order=False)
    assert d_nat.n_exchanges == 3 and d_tr.n_exchanges == 2
    assert (d_nat.collective_bytes_per_device
            == 3 * d_nat.bytes_per_exchange_per_device)
    assert (d_tr.collective_bytes_per_device
            == 2 * d_tr.bytes_per_exchange_per_device)
    n = jax.device_count() ** 2 * 64
    p_nat = fft_api.plan(kind="c2c", n=n, mesh=mesh,
                         placement="distributed", natural_order=True,
                         overlap="off")
    p_tr = fft_api.plan(kind="c2c", n=n, mesh=mesh,
                        placement="distributed", natural_order=False,
                        overlap="off")
    assert p_tr.collective_bytes * 3 == p_nat.collective_bytes * 2


def test_distributed_transposed_out_inverse_raises(mesh):
    # the conjugation identity is only the true inverse when the forward
    # returned natural order; TRANSPOSED_OUT plans must fail fast
    p = fft_api.plan(kind="c2c", n=jax.device_count() ** 2 * 16, mesh=mesh,
                     placement="distributed", natural_order=False)
    y = jnp.zeros((p.n,), jnp.float32)
    with pytest.raises(NotImplementedError, match="natural_order"):
        p.execute_inverse(y, y)


def test_plan_cache_thread_safe():
    # map-only jobs plan() from ThreadPoolExecutor workers: concurrent
    # same-spec calls must all get the one cached plan
    from concurrent.futures import ThreadPoolExecutor
    fft_api.clear_plan_cache()
    with ThreadPoolExecutor(max_workers=8) as ex:
        plans = list(ex.map(
            lambda _: fft_api.plan(kind="c2c", n=128, batch_shape=(2,)),
            range(32)))
    assert all(p is plans[0] for p in plans)
    info = fft_api.cache_info()
    assert info["misses"] == 1 and info["hits"] == 31


def test_interpret_none_and_explicit_bool_share_a_plan():
    # interpret=None resolves to a concrete bool before the cache key, so
    # library callers (None) and tests (explicit) reuse one compiled plan
    auto = fft_api.plan(kind="c2c", n=128, batch_shape=(2,))
    explicit = fft_api.plan(kind="c2c", n=128, batch_shape=(2,),
                            interpret=jax.default_backend() != "tpu")
    assert explicit is auto


# ---------------------------------------------------------------------------
# analytic cost model folds the roofline byte counters


def test_cost_model_folds_byte_counters():
    for n in (4096, 32768):
        pc = fft_api.plan(kind="c2c", n=n, batch_shape=(4,))
        assert pc.hbm_bytes_per_row == kplan.fft_hbm_bytes(n, "zero_copy")
        assert pc.hbm_bytes == 4 * pc.hbm_bytes_per_row
        assert pc.gemm_macs_per_row == kplan.make_plan(n).gemm_macs
        pr = fft_api.plan(kind="r2c", n=n, batch_shape=(4,))
        assert pr.hbm_bytes_per_row == kplan.rfft_hbm_bytes(n)
        assert pr.flops_per_row < pc.flops_per_row
    pcopy = fft_api.plan(kind="c2c", n=32768, batch_shape=(4,),
                         layout="copy")
    assert pcopy.hbm_bytes_per_row == kplan.fft_hbm_bytes(32768, "copy")


# ---------------------------------------------------------------------------
# execute_async: the stream executor's launch entry (no sync, donate)


def test_execute_async_matches_execute(rng):
    p = fft_api.plan(kind="c2c", n=256, batch_shape=(4,))
    xr = rng.standard_normal((4, 256)).astype(np.float32)
    xi = rng.standard_normal((4, 256)).astype(np.float32)
    want_r, want_i = p.execute(jnp.asarray(xr), jnp.asarray(xi))
    got_r, got_i = p.execute_async(xr, xi)
    np.testing.assert_array_equal(np.asarray(want_r), np.asarray(got_r))
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(got_i))


def test_execute_async_donate_zero_retrace_on_repeat(rng):
    fft_api.clear_plan_cache()
    p = fft_api.plan(kind="c2c", n=256, batch_shape=(3,))
    want = np.asarray(p.execute_async(
        rng.standard_normal((3, 256)).astype(np.float32),
        rng.standard_normal((3, 256)).astype(np.float32), donate=True)[0])
    assert p.trace_counts["forward"] == 1
    for _ in range(3):  # repeats reuse the donated executable: no retrace
        xr = rng.standard_normal((3, 256)).astype(np.float32)
        xi = rng.standard_normal((3, 256)).astype(np.float32)
        ref_r, _ = np.fft.fft(xr + 1j * xi).real, None
        got = p.execute_async(xr, xi, donate=True)
        np.testing.assert_allclose(np.asarray(got[0]), ref_r,
                                   rtol=2e-4, atol=2e-3)
    assert p.trace_counts["forward"] == 1
    assert want is not None
    # the plain executable is a second (also cached-once) trace
    xr = rng.standard_normal((3, 256)).astype(np.float32)
    p.execute(jnp.asarray(xr), jnp.asarray(xr))
    assert p.trace_counts["forward"] == 2


def test_execute_async_r2c_and_arity_errors(rng):
    p = fft_api.plan(kind="r2c", n=256, batch_shape=(2,))
    x = rng.standard_normal((2, 256)).astype(np.float32)
    want = p.execute_real(jnp.asarray(x))
    got = p.execute_async(x)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    with pytest.raises(ValueError, match="1 operand"):
        p.execute_async(x, x)
    pc = fft_api.plan(kind="c2c", n=256, batch_shape=(2,))
    with pytest.raises(ValueError, match="2 operand"):
        pc.execute_async(x)
    with pytest.raises(ValueError, match="execute_async"):
        pc.execute_async(x[:, :128], x[:, :128])
