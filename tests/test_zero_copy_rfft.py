"""Zero-copy four-step + real-input fast path invariants (DESIGN.md §3-4).

Covers the three tentpole claims:
  * the zero-copy layout is numerically identical (bitwise) to the legacy
    reshape+swapaxes path it replaces;
  * no standalone transpose op remains between the two leaf passes — the
    traced program is reshapes + pallas_calls only;
  * rfft/irfft match numpy's real-input transforms in every regime
    (tiny fallback, fused leaf epilogue, level-1 host untangle) and the
    byte counters show the expected savings.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fft import ops, plan
from repro.kernels.fft.matfft import matfft_cols


def _rel_err(got_r, got_i, want_r, want_i):
    scale = float(np.abs(np.asarray(want_r)).max()
                  + np.abs(np.asarray(want_i)).max()) or 1.0
    return max(float(np.abs(got_r - want_r).max()),
               float(np.abs(got_i - want_i).max())) / scale


# ---------------------------------------------------------------------------
# zero-copy four-step


@pytest.mark.parametrize("n", [32768, 1 << 16])
def test_zero_copy_bitmatches_copy_layout(rng, n):
    """Same GEMMs, same per-row reduction order -> bitwise-equal planes."""
    xr = rng.standard_normal((2, n)).astype(np.float32)
    xi = rng.standard_normal((2, n)).astype(np.float32)
    zr, zi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), layout="zero_copy")
    cr, ci = ops.fft(jnp.asarray(xr), jnp.asarray(xi), layout="copy")
    assert np.array_equal(np.asarray(zr), np.asarray(cr))
    assert np.array_equal(np.asarray(zi), np.asarray(ci))


@pytest.mark.parametrize("n", [32768])
def test_zero_copy_matches_numpy(rng, n):
    xr = rng.standard_normal((3, n)).astype(np.float32)
    xi = rng.standard_normal((3, n)).astype(np.float32)
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), layout="zero_copy")
    want = np.fft.fft(xr + 1j * xi)
    assert _rel_err(np.asarray(yr), np.asarray(yi),
                    want.real, want.imag) < 5e-6


def _top_level_primitives(fn, *args):
    return [str(eqn.primitive) for eqn in jax.make_jaxpr(fn)(*args).eqns]


def test_no_transpose_between_leaf_passes():
    """The zero-copy level-1 program is reshapes + pallas_calls ONLY: the
    column-strided BlockSpecs absorbed all three host transposes. The
    legacy layout must still show them (it's the measured baseline)."""
    n = 32768
    a = jnp.zeros((2, n), jnp.float32)

    prims = _top_level_primitives(
        lambda xr, xi: ops.fft(xr, xi, layout="zero_copy"), a, a)
    assert prims.count("pallas_call") == 2
    assert "transpose" not in prims, prims

    legacy = _top_level_primitives(
        lambda xr, xi: ops.fft(xr, xi, layout="copy"), a, a)
    assert "transpose" in legacy


def test_zero_copy_ragged_batch_tile(rng):
    """A non-pow2 batch_tile must not drop columns (regression: a ragged
    col tile left trailing output blocks unwritten -> NaN)."""
    n = 32768
    xr = rng.standard_normal((1, n)).astype(np.float32)
    xi = rng.standard_normal((1, n)).astype(np.float32)
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), layout="zero_copy",
                     batch_tile=24)
    want = np.fft.fft(xr + 1j * xi)
    assert _rel_err(np.asarray(yr), np.asarray(yi),
                    want.real, want.imag) < 5e-6


def test_fft_cols_matches_transposed_fft(rng):
    """fft_cols == fft(x.T) without the materialized transpose."""
    L, C = 512, 64
    xr = rng.standard_normal((L, C)).astype(np.float32)
    xi = rng.standard_normal((L, C)).astype(np.float32)
    yr, yi = ops.fft_cols(jnp.asarray(xr), jnp.asarray(xi))
    wr, wi = ops.fft(jnp.asarray(xr.T.copy()), jnp.asarray(xi.T.copy()))
    assert yr.shape == (C, L)
    assert _rel_err(np.asarray(yr), np.asarray(yi),
                    np.asarray(wr), np.asarray(wi)) < 5e-6
    prims = _top_level_primitives(
        lambda a, b: ops.fft_cols(a, b), jnp.asarray(xr), jnp.asarray(xi))
    assert "transpose" not in prims, prims


@pytest.mark.parametrize("out_major", ["row", "col"])
def test_matfft_cols_epilogue_and_layouts(rng, out_major):
    """Column kernel with the fused level-1 twiddle epilogue (row (b, c)
    gets W_{L*C}^{c * k}) == transpose + fft + multiply."""
    B, L, C = 2, 256, 16
    xr = rng.standard_normal((B, L, C)).astype(np.float32)
    xi = rng.standard_normal((B, L, C)).astype(np.float32)
    yr, yi = matfft_cols(jnp.asarray(xr), jnp.asarray(xi),
                         out_major=out_major, global_twiddle=(L * C, 0, C))
    # oracle: batched fft of the transposed columns, then the row multiply
    cols_r = np.swapaxes(xr, 1, 2).reshape(B * C, L)
    cols_i = np.swapaxes(xi, 1, 2).reshape(B * C, L)
    fr, fi = (np.asarray(a) for a in
              ops.fft(jnp.asarray(cols_r), jnp.asarray(cols_i)))
    t = np.tile(np.exp(-2j * np.pi * np.outer(np.arange(C), np.arange(L))
                       / (L * C)), (B, 1))
    w = (fr + 1j * fi) * t
    wr, wi = w.real, w.imag
    if out_major == "col":
        wr = np.swapaxes(wr.reshape(B, C, L), 1, 2)
        wi = np.swapaxes(wi.reshape(B, C, L), 1, 2)
    assert yr.shape == wr.shape
    assert _rel_err(np.asarray(yr), np.asarray(yi), wr, wi) < 5e-6


# ---------------------------------------------------------------------------
# real-input fast path


# 2: fallback; 8..16384: fused leaf epilogue (n//2 <= MAX_LEAF covers up to
# 32768); 65536: level-1 half-length transform + host untangle.
@pytest.mark.parametrize("n", [2, 8, 256, 1024, 8192, 32768, 1 << 16])
def test_rfft_matches_numpy(rng, n):
    x = rng.standard_normal((3, n)).astype(np.float32)
    yr, yi = ops.rfft(jnp.asarray(x))
    want = np.fft.rfft(x)
    assert yr.shape == (3, n // 2 + 1)
    assert _rel_err(np.asarray(yr), np.asarray(yi),
                    want.real, want.imag) < 5e-6


@pytest.mark.parametrize("n", [8, 1024, 32768, 1 << 16])
def test_irfft_roundtrip(rng, n):
    x = rng.standard_normal((2, n)).astype(np.float32)
    yr, yi = ops.rfft(jnp.asarray(x))
    back = ops.irfft(yr, yi)
    assert back.shape == x.shape
    assert float(jnp.abs(back - x).max()) / np.abs(x).max() < 1e-5


def test_irfft_matches_numpy(rng):
    """irfft of a spectrum we did NOT produce (independent oracle)."""
    n = 1024
    spec = (rng.standard_normal((2, n // 2 + 1))
            + 1j * rng.standard_normal((2, n // 2 + 1)))
    spec[:, 0] = spec[:, 0].real
    spec[:, -1] = spec[:, -1].real
    got = ops.irfft(jnp.asarray(spec.real.astype(np.float32)),
                    jnp.asarray(spec.imag.astype(np.float32)))
    want = np.fft.irfft(spec, n)
    assert float(np.abs(np.asarray(got) - want).max()) \
        / np.abs(want).max() < 1e-5


def test_rfft_real_bins(rng):
    """DC and Nyquist bins of a real signal are real."""
    x = rng.standard_normal((4, 512)).astype(np.float32)
    yr, yi = ops.rfft(jnp.asarray(x))
    scale = float(np.abs(np.asarray(yr)).max())
    assert float(jnp.abs(yi[:, 0]).max()) / scale < 1e-5
    assert float(jnp.abs(yi[:, -1]).max()) / scale < 1e-5


def test_rfft_single_pallas_call():
    """Fused-leaf rfft is ONE kernel: pack and untangle never touch HBM."""
    prims = _top_level_primitives(lambda x: ops.rfft(x),
                                  jnp.zeros((4, 4096), jnp.float32))
    assert prims.count("pallas_call") == 1
    assert "transpose" not in prims


# ---------------------------------------------------------------------------
# byte counters (the benchmark/acceptance arithmetic)


def test_fused_untangle_flag_matches_byte_counters():
    """The PR-1 limit regime is now explicit: `plan.fused_untangle` says
    whether the rfft untangle fused into one leaf kernel, and the byte
    counters must agree with the flag in both regimes (DESIGN.md §4)."""
    import repro.fft as fft_api

    for n in [8, 4096, 8192, 32768]:  # n//2 <= MAX_LEAF: fused epilogue
        p = fft_api.plan(kind="r2c", n=n, batch_shape=(1,))
        assert p.fused_untangle, n
        # one kernel: read the real plane, write the one-sided spectrum
        assert plan.rfft_hbm_bytes(n) == 4 * n + 2 * 4 * (n // 2 + 1)
        assert p.hbm_bytes_per_row == plan.rfft_hbm_bytes(n)

    for n in [1 << 16, 1 << 17]:  # n > 2*MAX_LEAF: host pack + untangle
        p = fft_api.plan(kind="r2c", n=n, batch_shape=(1,))
        assert not p.fused_untangle, n
        m = n // 2
        pack = 4 * n + 2 * 4 * m
        untangle = 2 * 2 * 4 * m + 2 * 4 * (m + 1)
        assert plan.rfft_hbm_bytes(n) == \
            pack + plan.fft_hbm_bytes(m, "zero_copy") + untangle
        assert p.hbm_bytes_per_row == plan.rfft_hbm_bytes(n)

    # c2c plans never untangle
    assert not fft_api.plan(kind="c2c", n=4096,
                            batch_shape=(1,)).fused_untangle


def test_hbm_byte_counters():
    for n in [32768, 1 << 16, 1 << 20]:
        assert plan.fft_hbm_bytes(n, "zero_copy") < plan.fft_hbm_bytes(n, "copy")
        # 4 traversals vs 10
        assert plan.fft_hbm_bytes(n, "zero_copy") * 10 \
            == plan.fft_hbm_bytes(n, "copy") * 4
    # leaf sizes: single pass, layouts identical
    assert plan.fft_hbm_bytes(4096, "zero_copy") == plan.fft_hbm_bytes(4096, "copy")
    # fused rfft regime: ~half the bytes of the complex transform
    for n in [4096, 8192, 32768]:
        assert plan.rfft_hbm_bytes(n) <= 0.55 * plan.fft_hbm_bytes(n)
