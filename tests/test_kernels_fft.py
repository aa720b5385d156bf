"""Per-kernel allclose sweeps + hypothesis property tests for the FFT stack."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.fft import ops, plan, ref
from repro.kernels.fft.matfft import matfft, matfft_cols, rfft_leaf
from repro.kernels.fft.stockham import stockham_fft


def _rel_err(got_r, got_i, want_r, want_i):
    scale = float(np.abs(np.asarray(want_r)).max()
                  + np.abs(np.asarray(want_i)).max()) or 1.0
    return max(float(np.abs(got_r - want_r).max()),
               float(np.abs(got_i - want_i).max())) / scale


# ---------------------------------------------------------------------------
# shape sweeps vs the jnp.fft oracle


@pytest.mark.parametrize("impl", ["matfft", "stockham"])
@pytest.mark.parametrize("n", [2, 4, 16, 128, 256, 512, 1024, 4096])
@pytest.mark.parametrize("rows", [1, 3, 8, 17])
def test_kernel_matches_oracle(rng, impl, n, rows):
    xr = rng.standard_normal((rows, n)).astype(np.float32)
    xi = rng.standard_normal((rows, n)).astype(np.float32)
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi), impl=impl)
    wr, wi = ref.fft_ref(jnp.asarray(xr), jnp.asarray(xi))
    assert _rel_err(yr, yi, wr, wi) < 5e-6


@pytest.mark.parametrize("n", [32768, 1 << 16])
def test_level1_four_step_matches_oracle(rng, n):
    xr = rng.standard_normal((2, n)).astype(np.float32)
    xi = rng.standard_normal((2, n)).astype(np.float32)
    yr, yi = ops.fft(jnp.asarray(xr), jnp.asarray(xi))
    wr, wi = ref.fft_ref(jnp.asarray(xr), jnp.asarray(xi))
    assert _rel_err(yr, yi, wr, wi) < 5e-6


def test_four_step_ref_algebra(rng):
    """The pure-jnp Bailey reference must equal jnp.fft exactly."""
    xr = rng.standard_normal((4, 1024)).astype(np.float32)
    xi = rng.standard_normal((4, 1024)).astype(np.float32)
    yr, yi = ref.four_step_ref(jnp.asarray(xr), jnp.asarray(xi), 32, 32)
    wr, wi = ref.fft_ref(jnp.asarray(xr), jnp.asarray(xi))
    assert _rel_err(yr, yi, wr, wi) < 5e-6


@pytest.mark.parametrize("n", [256, 1024])
def test_epilogue_fusion_matches_unfused(rng, n):
    """Fused on-the-fly twiddle epilogue == separate multiply (the
    HBM-saving path): row r, bin k gets W_N^{((3 + r) mod 8) * k}."""
    rows, period, n_global, row_off = 32, 8, 8 * n, 3
    xr = rng.standard_normal((rows, n)).astype(np.float32)
    xi = rng.standard_normal((rows, n)).astype(np.float32)
    fr, fi = matfft(jnp.asarray(xr), jnp.asarray(xi),
                    global_twiddle=(n_global, jnp.int32(row_off), period))
    yr, yi = matfft(jnp.asarray(xr), jnp.asarray(xi))
    g = (row_off + np.arange(rows)) % period
    t = np.exp(-2j * np.pi * np.outer(g, np.arange(n)) / n_global)
    w = (np.asarray(yr) + 1j * np.asarray(yi)) * t
    assert _rel_err(np.asarray(fr), np.asarray(fi), w.real, w.imag) < 5e-6


def _count_dots(jaxpr) -> int:
    """dot_general equations in a jaxpr and every jaxpr nested in it."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == "dot_general"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count += _count_dots(sub)
    return count


def _kernel_dots(fn, *args) -> int:
    """dot_general equations inside the one pallas_call ``fn`` traces."""
    calls = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return _count_dots(calls[0].params["jaxpr"])


_ROWS = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
_DIRECT = jax.ShapeDtypeStruct((8, 256), jnp.float32)
_COLS = jax.ShapeDtypeStruct((1, 1024, 128), jnp.float32)
_REAL = jax.ShapeDtypeStruct((8, 2048), jnp.float32)


@pytest.mark.parametrize("fn, args", [
    (lambda a, b: matfft(a, b, interpret=True), (_ROWS, _ROWS)),
    (lambda a, b: matfft(a, b, interpret=True), (_DIRECT, _DIRECT)),
    (lambda a, b: matfft_cols(a, b, interpret=True), (_COLS, _COLS)),
    (lambda x: rfft_leaf(x, interpret=True), (_REAL,)),
], ids=["matfft_1024", "dft_direct_256", "matfft_cols_1024", "rfft_2048"])
def test_complex_gemm_takes_three_real_dots(fn, args):
    """Each kernel runs one complex GEMM per tile, as three real dots."""
    assert _kernel_dots(fn, *args) == 3


# max_rel_l2 of the paper_capture_c2c1024 configuration
_CAPTURE_LIMIT = 1e-6


def _worst_row_rel_l2(got_r, got_i, x):
    want = np.fft.fft(x.astype(np.complex128), axis=-1)
    got = np.asarray(got_r, np.float64) + 1j * np.asarray(got_i, np.float64)
    return float((np.linalg.norm(got - want, axis=-1)
                  / np.linalg.norm(want, axis=-1)).max())


@pytest.mark.parametrize("kind, n", [
    ("rows", 256), ("rows", 1024), ("rows", 16384), ("cols", 1024)])
def test_matfft_within_capture_limit(kind, n):
    """Worst-row relative L2 error against float64 numpy stays within the
    paper_capture_c2c1024 configuration's ``max_rel_l2``. Interpret mode
    takes every dot in exact f32, so this guards the three-product form's
    algebra and its f32 tables, not the MXU's precision on a chip."""
    r = np.random.default_rng(n)
    if kind == "rows":
        x = (r.standard_normal((8, n))
             + 1j * r.standard_normal((8, n))).astype(np.complex64)
        yr, yi = matfft(jnp.asarray(x.real), jnp.asarray(x.imag),
                        interpret=True)
    else:
        # (B, L, C) columns: logical row b*C + c is x[b, :, c]
        x3 = (r.standard_normal((1, n, 128))
              + 1j * r.standard_normal((1, n, 128))).astype(np.complex64)
        yr, yi = matfft_cols(jnp.asarray(x3.real), jnp.asarray(x3.imag),
                             interpret=True)
        x = x3[0].T
    assert _worst_row_rel_l2(yr, yi, x) <= _CAPTURE_LIMIT


def test_dtype_is_float32(rng):
    yr, yi = ops.fft(jnp.ones((2, 64)), jnp.zeros((2, 64)))
    assert yr.dtype == jnp.float32 and yi.dtype == jnp.float32


# ---------------------------------------------------------------------------
# hypothesis property tests


@settings(max_examples=20, deadline=None)
@given(logn=st.integers(1, 11), rows=st.integers(1, 5), seed=st.integers(0, 99))
def test_linearity(logn, rows, seed):
    n = 1 << logn
    r = np.random.default_rng(seed)
    a = r.standard_normal((rows, n)).astype(np.float32)
    b = r.standard_normal((rows, n)).astype(np.float32)
    fa = ops.fft(jnp.asarray(a), jnp.zeros_like(jnp.asarray(a)))
    fb = ops.fft(jnp.asarray(b), jnp.zeros_like(jnp.asarray(b)))
    fab = ops.fft(jnp.asarray(a + 2 * b), jnp.zeros((rows, n), jnp.float32))
    want_r = np.asarray(fa[0]) + 2 * np.asarray(fb[0])
    want_i = np.asarray(fa[1]) + 2 * np.asarray(fb[1])
    assert _rel_err(np.asarray(fab[0]), np.asarray(fab[1]), want_r, want_i) < 1e-5


@settings(max_examples=20, deadline=None)
@given(logn=st.integers(1, 11), seed=st.integers(0, 99))
def test_parseval(logn, seed):
    n = 1 << logn
    r = np.random.default_rng(seed)
    x = r.standard_normal((2, n)).astype(np.float32)
    y = r.standard_normal((2, n)).astype(np.float32)
    fr, fi = ops.fft(jnp.asarray(x), jnp.asarray(y))
    time_e = np.sum(x * x + y * y)
    freq_e = float(jnp.sum(fr * fr + fi * fi)) / n
    assert abs(time_e - freq_e) / time_e < 1e-4


@settings(max_examples=15, deadline=None)
@given(logn=st.integers(1, 11), seed=st.integers(0, 99))
def test_ifft_roundtrip(logn, seed):
    n = 1 << logn
    r = np.random.default_rng(seed)
    x = r.standard_normal((3, n)).astype(np.float32)
    y = r.standard_normal((3, n)).astype(np.float32)
    fr, fi = ops.fft(jnp.asarray(x), jnp.asarray(y))
    br, bi = ops.ifft(fr, fi)
    scale = np.abs(x).max()
    assert float(jnp.abs(br - x).max()) / scale < 1e-5
    assert float(jnp.abs(bi - y).max()) / scale < 1e-5


@settings(max_examples=10, deadline=None)
@given(logn=st.integers(3, 10), k=st.integers(0, 7))
def test_impulse_response(logn, k):
    """FFT of a delta at k is exp(-2pi i k o / n)."""
    n = 1 << logn
    k = k % n
    x = np.zeros((1, n), np.float32)
    x[0, k] = 1.0
    fr, fi = ops.fft(jnp.asarray(x), jnp.zeros_like(jnp.asarray(x)))
    o = np.arange(n)
    ang = -2 * np.pi * k * o / n
    assert np.abs(np.asarray(fr)[0] - np.cos(ang)).max() < 1e-4
    assert np.abs(np.asarray(fi)[0] - np.sin(ang)).max() < 1e-4


# ---------------------------------------------------------------------------
# planning invariants


@settings(max_examples=50, deadline=None)
@given(p=st.integers(1, 28))
def test_split_pow2_invariants(p):
    n = 1 << p
    if n > plan.MAX_LEAF ** 2:
        return
    n1, n2 = plan.split_pow2(n, plan.MAX_LEAF)
    assert n1 * n2 == n
    assert n1 <= plan.MAX_LEAF and n2 <= plan.MAX_LEAF
    assert plan.is_pow2(n1) and plan.is_pow2(n2)


def test_dft_matrix_unitary():
    n = 64
    wr, wi = plan.dft_matrix(n)
    w = wr + 1j * wi
    assert np.abs(w @ w.conj().T / n - np.eye(n)).max() < 1e-5


@pytest.mark.parametrize("n", [2, 128, 256])
def test_gauss_dft_matrix_rebuilds_dft_matrix(n):
    """(wr, wi - wr, wr + wi) recombine to the planar DFT matrix."""
    wr, wd, ws = (a.astype(np.float64) for a in plan.gauss_dft_matrix(n))
    dr, di = plan.dft_matrix(n)
    assert np.array_equal(wr, dr)
    assert np.abs((wd + ws) / 2 - di).max() < 1e-7
    assert np.abs((ws - wd) / 2 - wr).max() < 1e-7


def test_stockham_twiddle_packing():
    n = 256
    offs = plan.stockham_stage_offsets(n)
    assert offs[0] == (0, n // 2, 1)
    assert sum(l for _, l, _ in offs) == n - 1
