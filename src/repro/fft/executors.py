"""Execution bodies behind `repro.fft` plans: the level-0/1 transform code.

This module is the *mechanism* layer of the plan-and-execute facade: plain
functions over planar float32 arrays that drive the Pallas kernels
(`kernels/fft/matfft.py`, `kernels/fft/stockham.py`). It holds what used to
be the bodies of `kernels.fft.ops` before the facade existed; `ops.*` is
now a set of deprecated shims over `repro.fft.plan`.

Hierarchy (mirrors the paper's block decomposition, DESIGN.md §2):

  level 0  (VMEM/MXU)   matfft kernel, n <= plan.MAX_LEAF
  level 1  (HBM, here)  host four-step n = n1*n2, leaf = level 0, with the
                        outer twiddle FUSED into the first leaf's epilogue
  level 2  (ICI)        cross-device four-step — core/fft/distributed.py,
                        which calls back into these executors for local work

The ``layout`` option selects how level-1 pass boundaries move data
(DESIGN.md §3):

  "zero_copy" (default)  column-strided Pallas kernels read/write the
                         natural buffers directly; no transposed tensor is
                         ever materialized in HBM
  "copy"                 the legacy reshape+swapaxes path, kept as the
                         measured baseline (benchmarks/bench_fft.py) and
                         as the fallback for non-matfft leaf impls

``interpret=None`` auto-selects interpret mode off-TPU so the same code
runs on this CPU container and on real hardware.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from repro.kernels.fft import plan as fft_plan
from repro.kernels.fft import ref as fft_ref
from repro.kernels.fft.matfft import (matfft, matfft_cols,
                                      resolve_interpret, rfft_leaf,
                                      rfft_pack_leaf, split_twiddle,
                                      untangle_half_spectrum)
from repro.kernels.fft.matfft import twiddle as twiddle_planes
from repro.kernels.fft.stockham import stockham_fft

Planar = tuple[jnp.ndarray, jnp.ndarray]


def _leaf(xr, xi, impl: str, interpret: bool, twiddle=None,
          batch_tile=None):
    """One row-major leaf pass; ``twiddle`` is matfft's ``global_twiddle``
    (fused in the kernel for matfft, an elementwise multiply otherwise)."""
    if impl == "matfft":
        return matfft(xr, xi, global_twiddle=twiddle, batch_tile=batch_tile,
                      interpret=interpret)
    if impl == "stockham":
        yr, yi = stockham_fft(xr, xi, batch_tile=batch_tile,
                              interpret=interpret)
    elif impl == "ref":
        yr, yi = fft_ref.fft_ref(xr, xi)
    else:
        raise ValueError(f"unknown fft impl {impl!r}")
    if twiddle is None:
        return yr, yi
    n_global, row_off, period = split_twiddle(twiddle)
    row = (jnp.asarray(row_off, jnp.int32).reshape(-1)[0]
           + jnp.arange(yr.shape[0], dtype=jnp.int32)[:, None])
    if period:
        row = row & (period - 1)
    tr, ti = twiddle_planes(row, jnp.arange(yr.shape[1], dtype=jnp.int32),
                            n_global)
    return yr * tr - yi * ti, yr * ti + yi * tr


# ---------------------------------------------------------------------------
# the shared axis-pass primitive: every multi-axis transform in the repo —
# the level-1 four-step, true N-D fftn/rfftn, and the distributed pass
# boundaries (via fft_cols) — is a chain of these


def axis_pass(xr: jnp.ndarray, xi: jnp.ndarray, view, *,
              out_major: str = "row", global_twiddle=None,
              impl: str = "matfft", interpret: bool | None = None,
              col_tile: int | None = None, col_offset: int = 0,
              ncols: int | None = None, layout: str = "zero_copy") -> Planar:
    """FFT along the MIDDLE axis of a planar ``view = (B, L, C)`` reshape.

    The single shared primitive behind every multi-pass transform: "FFT one
    axis of a 2-D view, with optional twiddle, with row/col-major store".
    ``out_major="row"`` returns (B*nc, L) with row index b*nc + c;
    ``out_major="col"`` returns (B, L, nc) — the result written back in
    column order, i.e. the transformed axis stays where it was, which is
    what keeps a chain of passes transpose-free in HBM.

    ``global_twiddle`` (see `matfft.matfft`) multiplies output row (b, c)
    by the four-step's outer twiddle. ``col_offset``/``ncols`` select an
    aligned column slab fetched in place from the full operand (the
    overlapped exchange engines' slab reads).

    layout="zero_copy" + impl="matfft" runs the column-strided Pallas
    kernel (`matfft_cols`); anything else falls back to a materialized
    transpose around the row-major leaf (the measured "copy" baseline).
    """
    B, L, C = view
    xr3 = xr.reshape(B, L, C)
    xi3 = xi.reshape(B, L, C)
    nc = C - col_offset if ncols is None else ncols
    if (layout == "zero_copy" and impl == "matfft" and L > 1
            and fft_plan.is_pow2(C) and fft_plan.is_pow2(nc)
            and fft_plan.make_plan(L).levels == 1):
        return matfft_cols(xr3, xi3, out_major=out_major,
                           global_twiddle=global_twiddle, col_tile=col_tile,
                           col_offset=col_offset, ncols=nc,
                           interpret=resolve_interpret(interpret))
    # fallback: materialize the transpose; columns become batch rows
    if col_offset or nc != C:
        xr3 = xr3[:, :, col_offset:col_offset + nc]
        xi3 = xi3[:, :, col_offset:col_offset + nc]
    xrt = xr3.swapaxes(1, 2).reshape(B * nc, L)
    xit = xi3.swapaxes(1, 2).reshape(B * nc, L)
    yr, yi = fft(xrt, xit, impl=impl, interpret=interpret,
                 batch_tile=col_tile, global_twiddle=global_twiddle,
                 layout=layout)
    if out_major == "col":
        return (yr.reshape(B, nc, L).swapaxes(1, 2),
                yi.reshape(B, nc, L).swapaxes(1, 2))
    return yr, yi


def four_step_zero_copy(xr: jnp.ndarray, xi: jnp.ndarray, n1: int, n2: int,
                        *, impl: str = "matfft",
                        col_tile: int | None = None,
                        interpret: bool | None = None) -> Planar:
    """Level-1 four-step re-expressed as two shared axis passes.

    Pass 1 transforms the n1-axis of the (rows, n1, n2) view with the outer
    twiddle W_N^{o1*i2} fused into the store (row-major out); pass 2
    transforms the n2-axis of the resulting (rows, n2, n1) view with a
    column-major store — which IS the o2-major final order. No transposed
    tensor is ever materialized in HBM (DESIGN.md §3): 4 traversals total
    vs the legacy 10 (plan.fft_hbm_bytes).
    """
    rows, n = xr.shape
    assert n == n1 * n2
    # pass-1 output row (b, i2) gets W_N^{i2 * o1}: the kernel computes it
    # in its epilogue (row index mod n2), so no twiddle table exists
    ar, ai = axis_pass(xr, xi, (rows, n1, n2), out_major="row",
                       global_twiddle=(n, 0, n2), impl=impl,
                       col_tile=col_tile,
                       interpret=interpret)  # (rows*n2, n1), row (b, i2)
    cr, ci = axis_pass(ar, ai, (rows, n2, n1), out_major="col", impl=impl,
                       col_tile=col_tile,
                       interpret=interpret)  # (rows, n2, n1) = [b, o2, o1]
    return cr.reshape(rows, n), ci.reshape(rows, n)


def fft(xr: jnp.ndarray, xi: jnp.ndarray, *, impl: str = "matfft",
        interpret: bool | None = None, batch_tile: int | None = None,
        global_twiddle=None, layout: str = "zero_copy") -> Planar:
    """Batched forward FFT along the last axis of planar float32 arrays.

    Any leading batch shape; last-axis length must be a power of two up to
    MAX_LEAF**2 (single device). Larger transforms go through
    core/fft/distributed.py.
    """
    if layout not in ("zero_copy", "copy"):
        raise ValueError(f"unknown layout {layout!r}")
    interpret = resolve_interpret(interpret)
    batch_shape, n = xr.shape[:-1], xr.shape[-1]
    if n == 1:
        return xr, xi
    fft_plan.log2i(n)
    rows = 1
    for d in batch_shape:
        rows *= d
    xr2 = xr.reshape(rows, n)
    xi2 = xi.reshape(rows, n)

    p = fft_plan.make_plan(n)
    if p.levels == 1:
        yr, yi = _leaf(xr2, xi2, impl, interpret, twiddle=global_twiddle,
                       batch_tile=batch_tile)
    else:
        if global_twiddle is not None:
            raise ValueError("global_twiddle requires a single-level plan")
        yr, yi = _four_step(xr2, xi2, p.n1, p.n2, impl, interpret, batch_tile,
                            layout)
    return yr.reshape(*batch_shape, n), yi.reshape(*batch_shape, n)


def _four_step(xr, xi, n1: int, n2: int, impl: str, interpret: bool,
               batch_tile: int | None, layout: str = "zero_copy") -> Planar:
    """Host-level four-step: two batched leaf passes.

    layout="zero_copy" (matfft only): both passes are column-strided Pallas
    kernels over free reshapes of the same buffers — no transposed tensor
    is ever materialized (four_step_zero_copy, on the shared axis_pass).

    layout="copy": the legacy path — three reshape+swapaxes transposes
    around two row-major leaf passes, each a full HBM round-trip. Pass 1
    still fuses the outer twiddle W_N^{o1*i2} into the leaf epilogue,
    computed on the fly: no twiddle table exists.
    """
    rows, n = xr.shape
    assert n == n1 * n2

    if layout == "zero_copy" and impl == "matfft":
        return four_step_zero_copy(xr, xi, n1, n2, impl=impl,
                                   col_tile=batch_tile, interpret=interpret)

    def to_cols(a):  # (rows, n1*n2) -> (rows*n2, n1)
        return a.reshape(rows, n1, n2).swapaxes(1, 2).reshape(rows * n2, n1)

    # row (b, i2) of the pass-1 output gets W_N^{i2 * o1} (row mod n2)
    ar, ai = _leaf(to_cols(xr), to_cols(xi), impl, interpret,
                   twiddle=(n, 0, n2), batch_tile=batch_tile)

    def to_rows(a):  # (rows*n2, n1) -> (rows*n1, n2)
        return a.reshape(rows, n2, n1).swapaxes(1, 2).reshape(rows * n1, n2)

    cr, ci = _leaf(to_rows(ar), to_rows(ai), impl, interpret,
                   batch_tile=batch_tile)

    def out_order(a):  # rows (b, o1), cols o2 -> flat o = o2*n1 + o1
        return a.reshape(rows, n1, n2).swapaxes(1, 2).reshape(rows, n)

    return out_order(cr), out_order(ci)


def fft_cols(xr: jnp.ndarray, xi: jnp.ndarray, *, impl: str = "matfft",
             interpret: bool | None = None, col_tile: int | None = None,
             global_twiddle=None, layout: str = "zero_copy",
             out_major: str = "row", col_offset: int = 0,
             ncols: int | None = None) -> Planar:
    """FFT each COLUMN of planar (L, C) arrays.

    Returns (C', L) row-major for ``out_major="row"`` or (L, C')
    column-major for ``out_major="col"`` (C' = ncols when a slab is
    selected). Semantically ``fft(xr.T, xi.T)`` (transposed again for
    "col"), but with layout="zero_copy" the column-strided Pallas kernel
    reads the operand in place and writes the requested layout directly —
    the materialized `.T` copies at distributed-FFT pass boundaries fold
    into the kernel (DESIGN.md §3).

    ``col_offset``/``ncols`` restrict the call to the column slab
    ``[col_offset, col_offset + ncols)``: on the zero-copy path the
    BlockSpec index map fetches the slab from the full operand in place
    (no retile); the fallback slices (it already materializes a copy).

    Thin wrapper over the shared `axis_pass` builder with a B=1 view.
    """
    L, C = xr.shape
    nc = C - col_offset if ncols is None else ncols
    yr, yi = axis_pass(xr, xi, (1, L, C), out_major=out_major,
                       global_twiddle=global_twiddle, impl=impl,
                       interpret=interpret, col_tile=col_tile,
                       col_offset=col_offset, ncols=nc, layout=layout)
    if out_major == "col":
        return yr.reshape(L, nc), yi.reshape(L, nc)
    return yr, yi


def ifft(xr: jnp.ndarray, xi: jnp.ndarray, **kw) -> Planar:
    """Inverse FFT via the conjugation identity: ifft(x) = conj(fft(conj(x)))/n."""
    n = xr.shape[-1]
    yr, yi = fft(xr, -xi, **kw)
    return yr / n, -yi / n


def rfft(x: jnp.ndarray, *, impl: str = "matfft",
         interpret: bool | None = None, batch_tile: int | None = None,
         layout: str = "zero_copy") -> Planar:
    """Real-input FFT; returns planar one-sided spectrum (n//2 + 1 bins).

    Fast path (impl="matfft", n >= 4): at leaf sizes one kernel reads the
    real rows and writes only the one-sided spectrum; above, n reals are
    packed as n/2 complex points, one half-length transform runs, and a
    vectorized jnp epilogue untangles the spectrum — ~half the HBM bytes
    of fft()+slice (DESIGN.md §4).
    """
    n = x.shape[-1]
    x = x.astype(jnp.float32)
    if n < 4 or impl != "matfft":
        # legacy path: full complex transform, slice the half spectrum
        yr, yi = fft(x, jnp.zeros_like(x), impl=impl, interpret=interpret,
                     batch_tile=batch_tile, layout=layout)
        return yr[..., : n // 2 + 1], yi[..., : n // 2 + 1]
    fft_plan.log2i(n)
    m = n // 2
    batch_shape = x.shape[:-1]
    rows = 1
    for d in batch_shape:
        rows *= d
    x2 = x.reshape(rows, n)
    if fft_plan.make_plan(m).levels == 1:
        yr, yi = rfft_leaf(x2, batch_tile=batch_tile,
                           interpret=resolve_interpret(interpret))
    else:
        # level-1: the untangle can't live inside one leaf tile (bin o
        # pairs with m - o, a different o1-block), so pack + untangle run
        # as host epilogues around the half-length zero-copy transform
        z = x2.reshape(rows, m, 2)
        zr, zi = fft(z[..., 0], z[..., 1], impl=impl, interpret=interpret,
                     batch_tile=batch_tile, layout=layout)
        vr, vi = (jnp.asarray(a) for a in fft_plan.rfft_twiddle(n))
        yr, yi = untangle_half_spectrum(zr, zi, vr, vi)
    return yr.reshape(*batch_shape, m + 1), yi.reshape(*batch_shape, m + 1)


def irfft(yr: jnp.ndarray, yi: jnp.ndarray, *, impl: str = "matfft",
          interpret: bool | None = None, batch_tile: int | None = None,
          layout: str = "zero_copy") -> jnp.ndarray:
    """Inverse of rfft: one-sided (..., n//2 + 1) spectrum -> real (..., n).

    Runs the packing trick in reverse: re-entangle the even/odd sub-spectra
    into a half-length spectrum, one half-length inverse transform, then
    interleave — the same ~2x saving as the forward fast path.
    """
    m = yr.shape[-1] - 1
    n = 2 * m
    if m < 2 or impl != "matfft":
        # legacy path: mirror to the full spectrum, full inverse transform
        fr = jnp.concatenate([yr, yr[..., -2:0:-1]], axis=-1)
        fi = jnp.concatenate([yi, -yi[..., -2:0:-1]], axis=-1)
        zr, _ = ifft(fr, fi, impl=impl, interpret=interpret,
                     batch_tile=batch_tile, layout=layout)
        return zr
    # E[k] = (X[k] + conj(X[m-k]))/2 ; O[k] = conj(v[k])*(X[k] - conj(X[m-k]))/2
    xr_, xi_ = yr[..., :m], yi[..., :m]
    pr, pi = yr[..., :0:-1], -yi[..., :0:-1]  # conj(X[m-k]), k = 0..m-1
    er, ei = 0.5 * (xr_ + pr), 0.5 * (xi_ + pi)
    dr, di = 0.5 * (xr_ - pr), 0.5 * (xi_ - pi)
    vr, vi = (jnp.asarray(a) for a in fft_plan.rfft_twiddle(n))
    our = vr * dr + vi * di  # conj(v) * D
    oui = vr * di - vi * dr
    # Z = E + i*O, z = IDFT_m(Z), x[2k] = Re z[k], x[2k+1] = Im z[k]
    zr, zi = ifft(er - oui, ei + our, impl=impl, interpret=interpret,
                  batch_tile=batch_tile, layout=layout)
    return jnp.stack([zr, zi], axis=-1).reshape(*zr.shape[:-1], n)


# ---------------------------------------------------------------------------
# true N-D transforms: axis passes, no outer twiddle (the DFT is separable)


def rfft_pack_pass(x2: jnp.ndarray, n_last: int, *, impl: str = "matfft",
                   interpret: bool | None = None,
                   batch_tile: int | None = None,
                   layout: str = "zero_copy") -> Planar:
    """Contiguous-axis pass of the rfftn fast path: (rows, n_last) real
    rows -> (rows, n_last//2) RAW packed half spectrum (no untangle).

    Shared by the local `rfftn` and the distributed r2c pencil
    (`core.fft.distributed.build_pencil_r2c`) so both issue literally the
    same kernels — the bitwise gate between them depends on it.
    """
    m = n_last // 2
    if fft_plan.make_plan(m).levels == 1:
        return rfft_pack_leaf(x2, batch_tile=batch_tile,
                              interpret=resolve_interpret(interpret))
    # n_last > 2*MAX_LEAF: the half transform is level-1; pack on the
    # host (one extra round trip, counted by plan.rfftn_hbm_bytes)
    z = x2.reshape(x2.shape[0], m, 2)
    return fft(z[..., 0], z[..., 1], impl=impl, interpret=interpret,
               batch_tile=batch_tile, layout=layout)


def _flip_leading(pr, pi, ndim: int, nd: int):
    """Index-negate (k -> (-k) mod n) every transformed axis but the last."""
    for ax in range(ndim - nd, ndim - 1):
        pr = jnp.roll(jnp.flip(pr, axis=ax), 1, axis=ax)
        pi = jnp.roll(jnp.flip(pi, axis=ax), 1, axis=ax)
    return pr, pi


def _untangle_nd(zr, zi, vr, vi, nd: int) -> Planar:
    """N-D untangle of the packed half spectrum AFTER the leading axes'
    DFTs have run on it.

    Same E/O algebra as `untangle_half_spectrum`, but conjugation is
    antilinear — it anticommutes with the leading-axis DFTs — so the
    Hermitian partner of bin (k0, .., k) sits at ((-k0) % n0, ..,
    (m-k) % m): flipped along EVERY transformed axis, not just the last.
    The Nyquist column m is no longer real for nd > 1 (only the full N-D
    Hermitian symmetry survives, not per-column realness).
    """
    pr, pi = _flip_leading(zr, zi, zr.ndim, nd)
    pr = jnp.roll(pr[..., ::-1], 1, axis=-1)
    pi = jnp.roll(pi[..., ::-1], 1, axis=-1)
    er, ei = 0.5 * (zr + pr), 0.5 * (zi - pi)
    our, oui = 0.5 * (zi + pi), 0.5 * (pr - zr)
    xr = er + vr * our - vi * oui
    xi = ei + vr * oui + vi * our
    nyq_r = er[..., :1] - our[..., :1]
    nyq_i = ei[..., :1] - oui[..., :1]
    return (jnp.concatenate([xr, nyq_r], axis=-1),
            jnp.concatenate([xi, nyq_i], axis=-1))


def fftn(xr: jnp.ndarray, xi: jnp.ndarray, shape, *, impl: str = "matfft",
         interpret: bool | None = None, batch_tile: int | None = None,
         layout: str = "zero_copy") -> Planar:
    """N-D forward FFT over the trailing ``len(shape)`` axes.

    The contiguous (last) axis runs the batched 1-D path (level 0/1, incl.
    the zero-copy four-step for long rows); every earlier axis is one
    shared `axis_pass` with a column-major store, so the data never leaves
    its natural layout — the whole chain is transpose-free in HBM
    (layout="zero_copy"). layout="copy" materializes a swapaxes round-trip
    per non-contiguous axis: the naive baseline benchmarks/bench_fft2.py
    gates against.
    """
    shape = tuple(int(d) for d in shape)
    nd = len(shape)
    if tuple(xr.shape[-nd:]) != shape:
        raise ValueError(
            f"operand trailing dims {tuple(xr.shape[-nd:])} do not match "
            f"transform shape {shape}")
    if nd == 1:
        return fft(xr, xi, impl=impl, interpret=interpret,
                   batch_tile=batch_tile, layout=layout)
    batch = xr.shape[:-nd]
    rows = math.prod(batch)
    yr, yi = fft(xr, xi, impl=impl, interpret=interpret,
                 batch_tile=batch_tile, layout=layout)
    for k in range(nd - 2, -1, -1):
        L = shape[k]
        inner = math.prod(shape[k + 1:])
        b = rows * math.prod(shape[:k])
        yr, yi = axis_pass(yr, yi, (b, L, inner), out_major="col",
                           impl=impl, interpret=interpret,
                           col_tile=batch_tile, layout=layout)
    return yr.reshape(*batch, *shape), yi.reshape(*batch, *shape)


def ifftn(xr: jnp.ndarray, xi: jnp.ndarray, shape, **kw) -> Planar:
    """Inverse N-D FFT via the global conjugation identity (/prod(shape))."""
    n_total = math.prod(int(d) for d in shape)
    yr, yi = fftn(xr, -xi, shape, **kw)
    return yr / n_total, -yi / n_total


def rfftn(x: jnp.ndarray, shape, *, impl: str = "matfft",
          interpret: bool | None = None, batch_tile: int | None = None,
          layout: str = "zero_copy") -> Planar:
    """N-D real-input FFT; one-sided over the contiguous axis.

    Returns planar ``(*batch, *shape[:-1], shape[-1]//2 + 1)`` — the
    numpy.fft.rfftn/rfft2 convention (r2c on the last axis).

    Fast path (impl="matfft", shape[-1] >= 4): the contiguous axis packs
    n reals as n/2 complex and transforms at half length WITHOUT the
    untangle (`rfft_pack_leaf` reads the real rows in the kernel — no
    even/odd planes in HBM); the remaining axes transform the half-width
    spectrum (the conjugate-symmetry untangle is a linear map on the last
    axis, so it commutes with the other axes' DFTs); ONE vectorized
    untangle epilogue widens m -> m+1 bins at the end. Every pass stays on
    pow2 widths — fully zero-copy.
    """
    shape = tuple(int(d) for d in shape)
    nd = len(shape)
    x = x.astype(jnp.float32)
    if nd == 1:
        return rfft(x, impl=impl, interpret=interpret,
                    batch_tile=batch_tile, layout=layout)
    n_last = shape[-1]
    if n_last < 4 or impl != "matfft":
        # legacy path: full complex N-D transform, slice the half spectrum
        yr, yi = fftn(x, jnp.zeros_like(x), shape, impl=impl,
                      interpret=interpret, batch_tile=batch_tile,
                      layout=layout)
        return yr[..., : n_last // 2 + 1], yi[..., : n_last // 2 + 1]
    fft_plan.log2i(n_last)
    m = n_last // 2
    batch = x.shape[:-nd]
    rows = math.prod(batch)
    half = (*shape[:-1], m)

    # pass over the contiguous axis: packed half-length transform, raw
    # (un-untangled) half spectrum out
    x2 = x.reshape(rows * math.prod(shape[:-1]), n_last)
    zr, zi = rfft_pack_pass(x2, n_last, impl=impl, interpret=interpret,
                            batch_tile=batch_tile, layout=layout)
    zr = zr.reshape(*batch, *half)
    zi = zi.reshape(*batch, *half)

    # remaining axes on the half-width spectrum (all pow2)
    for k in range(nd - 2, -1, -1):
        L = shape[k]
        inner = math.prod(half[k + 1:])
        b = rows * math.prod(shape[:k])
        zr, zi = axis_pass(zr, zi, (b, L, inner), out_major="col",
                           impl=impl, interpret=interpret,
                           col_tile=batch_tile, layout=layout)
        zr = zr.reshape(*batch, *half)
        zi = zi.reshape(*batch, *half)

    # one vectorized N-D untangle: m -> m + 1 bins
    vr, vi = (jnp.asarray(a) for a in fft_plan.rfft_twiddle(n_last))
    return _untangle_nd(zr, zi, vr, vi, nd)


def irfftn(yr: jnp.ndarray, yi: jnp.ndarray, shape, *, impl: str = "matfft",
           interpret: bool | None = None, batch_tile: int | None = None,
           layout: str = "zero_copy") -> jnp.ndarray:
    """Inverse of rfftn: one-sided spectrum -> real ``(*batch, *shape)``.

    Runs the forward factorization in reverse: re-entangle the one-sided
    bins into the half-length spectrum (pow2 width again), inverse
    transform the leading axes, then the half-length inverse + interleave
    on the contiguous axis — the same ~2x saving as the forward fast path.
    """
    shape = tuple(int(d) for d in shape)
    nd = len(shape)
    if nd == 1:
        return irfft(yr, yi, impl=impl, interpret=interpret,
                     batch_tile=batch_tile, layout=layout)
    n_last = shape[-1]
    m = n_last // 2
    if m < 2 or impl != "matfft":
        # legacy: inverse the leading axes as c2c via materialized
        # swapaxes, then the 1-D irfft on the contiguous axis
        for k in range(nd - 1):
            ax = k - nd  # negative axis index of shape[k] in the operand
            ar = jnp.swapaxes(yr, ax, -1)
            ai = jnp.swapaxes(yi, ax, -1)
            ar, ai = ifft(ar, ai, impl=impl, interpret=interpret,
                          batch_tile=batch_tile, layout=layout)
            yr = jnp.swapaxes(ar, ax, -1)
            yi = jnp.swapaxes(ai, ax, -1)
        return irfft(yr, yi, impl=impl, interpret=interpret,
                     batch_tile=batch_tile, layout=layout)
    batch = yr.shape[:-nd]
    rows = math.prod(batch)
    half = (*shape[:-1], m)

    # U^-1: re-entangle one-sided bins -> half-length spectrum. Same
    # algebra as irfft, but the Hermitian partner is flipped along every
    # transformed axis (see _untangle_nd): conj(X[(-k0) % n0, .., m-k]).
    xr_, xi_ = yr[..., :m], yi[..., :m]
    pr, pi = yr[..., :0:-1], -yi[..., :0:-1]  # conj partner, last axis
    pr, pi = _flip_leading(pr, pi, pr.ndim, nd)
    er, ei = 0.5 * (xr_ + pr), 0.5 * (xi_ + pi)
    dr, di = 0.5 * (xr_ - pr), 0.5 * (xi_ - pi)
    vr, vi = (jnp.asarray(a) for a in fft_plan.rfft_twiddle(n_last))
    our = vr * dr + vi * di  # conj(v) * D
    oui = vr * di - vi * dr
    zr, zi = er - oui, ei + our

    # leading-axis inverses on the pow2 half width (conjugation identity)
    for k in range(nd - 2, -1, -1):
        L = shape[k]
        inner = math.prod(half[k + 1:])
        b = rows * math.prod(shape[:k])
        ar, ai = axis_pass(zr, -zi, (b, L, inner), out_major="col",
                           impl=impl, interpret=interpret,
                           col_tile=batch_tile, layout=layout)
        zr = ar.reshape(*batch, *half) / L
        zi = -ai.reshape(*batch, *half) / L

    # contiguous axis: half-length inverse + interleave
    wr, wi = ifft(zr, zi, impl=impl, interpret=interpret,
                  batch_tile=batch_tile, layout=layout)
    return jnp.stack([wr, wi], axis=-1).reshape(*wr.shape[:-1], n_last)
