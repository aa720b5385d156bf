"""MXU matmul-DFT Pallas kernels (the primary FFT kernels).

Hardware adaptation (see DESIGN.md §2): CUFFT runs Cooley-Tukey butterflies
on scalar CUDA cores; a TPU's throughput lives in the MXU systolic array,
which only speaks GEMM. So the per-tile DFT is the Bailey four-step
*inside VMEM*, laid out for the (8, 128) vreg tiling that Mosaic lowers:

    (bt, n) tile = q lane-aligned (bt, 128) slabs, n = q * 128
      gathered in bit-reversed order into a (q, bt, 128) VMEM scratch
      -> q-point radix-2 DFT across the slabs (VPU, one vectorized
         butterfly per stage) -> twiddle
      -> one (q*bt, 128) @ (128, 128) complex GEMM (MXU, three real dots)
      -> reorder: each slab, transposed, is a strided sublane store into a
         (n, bt) VMEM scratch; one transpose returns the natural order

No step reshapes a lane axis into pieces narrower than 128, and every
array stays dense in its vregs. For n <= DIRECT_N the full (n, n) DFT
matrix is used instead (one complex GEMM).

Three kernel entry points share that tile math (DESIGN.md §3):

  * ``matfft``       row-major batch: (rows, n) in, (rows, n) out.
  * ``matfft_cols``  column-strided batch: transforms the MIDDLE axis of a
    (B, L, C) view. The BlockSpec index map fetches (1, L, ct) tiles, the
    transpose happens in VMEM, and the output is written either row-major
    or back in column order. Chaining two of these is the ZERO-COPY host
    four-step: no transposed tensor is ever materialized in HBM — the TPU
    analogue of the paper's "one allocate+memcpy pair per block" rule.
  * ``rfft_leaf``    real-input fast path: the same four-step on the
    natural (rows, n) real tile, keeping only the n/2 bins (plus Nyquist)
    of the one-sided spectrum, so nothing else leaves VMEM — about half
    the HBM bytes of the complex transform it replaces.

The dots run at HIGHEST precision: a single bf16 MXU pass would lose about
three digits. Every complex GEMM's right-hand side is a constant DFT
matrix W = Wr + i*Wi, so it takes three real products (the Gauss form),
not four:

    k1 = (xr + xi) Wr,   k2 = xr (Wi - Wr),   k3 = xi (Wr + Wi)
    re = k1 - k3,        im = k1 + k2

The sums Wi - Wr and Wr + Wi are tables, formed in float64 on the host
(plan.gauss_split); only xr + xi is added in the kernel.

The optional ``global_twiddle`` fuses the four-step's outer twiddle
multiply into the kernel's final store, computed in registers from the
row index: no twiddle table exists in HBM, and a kernel used as the leaf
of a host-level (or distributed-level) four-step saves one full HBM
round-trip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fft import plan as fft_plan

# Transform lengths up to this use one full DFT-matrix GEMM.
DIRECT_N = 256
# Target elements per (bt, n) row tile and per (L, ct) column tile. The
# v5e compiler sizes the resulting scoped VMEM at 2-18 MiB for matfft
# (n = 1024..16384), 3-34 MiB for matfft_cols (L = 1024..16384, ct = 128)
# and 20 MiB for rfft_leaf at n = 32768: all under _VMEM_LIMIT.
_TILE_ELEMS = 1 << 18
# Column blocks of at least this many elements are single-buffered.
_SINGLE_BUFFER_ELEMS = 1 << 20
# Scoped VMEM the kernels may use (v5e has 128 MiB per core; the
# compiler's default scope is 16 MiB).
_VMEM_LIMIT = 96 << 20

_dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` means: interpret only where there is no TPU to compile for."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _max_tile(n: int) -> int:
    """The four-step's strided stores need its (n, bt) reorder buffer to
    be at most one lane tile wide, so a tile holds at most 128 rows."""
    return 512 if n <= DIRECT_N else fft_plan.LANES


def default_batch_tile(n: int) -> int:
    return max(8, min(_max_tile(n), _TILE_ELEMS // max(n, 1)))


def _col_tile(L: int, nc: int, col_tile: int | None) -> int:
    """Columns per matfft_cols instance: a power of two dividing ``nc``,
    at most ``_max_tile(L)`` and, where ``nc`` allows, a whole multiple of
    the lane width (so at L > DIRECT_N it is exactly 128 or all of nc)."""
    ct = min(col_tile or _TILE_ELEMS // L, nc, _max_tile(L))
    # round down to a power of two so ct always divides nc (validated pow2):
    # a ragged tile would leave trailing output blocks unwritten
    ct = 1 << (ct.bit_length() - 1)
    return max(ct, min(fft_plan.LANES, nc))


def _pallas(kernel, *, grid, in_specs, out_specs, out_shape, interpret,
            name, scratch_shapes=()):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch_shapes),
        interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT))


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _cgemm(ar, ai, wr, wd, ws):
    """Planar complex GEMM (ar + i*ai) @ W with f32 accumulation, in three
    real MXU GEMMs. W is constant, given as wr = Re W, wd = Im W - Re W
    and ws = Re W + Im W (plan.gauss_split), so its sums cost nothing
    here: re = (ar + ai) wr - ai ws, im = (ar + ai) wr + ar wd."""
    k1 = _dot(ar + ai, wr)
    return k1 - _dot(ai, ws), k1 + _dot(ar, wd)


# ---------------------------------------------------------------------------
# shared in-VMEM tile DFT (used by every kernel entry point)


def _tile_dft_direct(xr, xi, wr, wd, ws):
    """Direct DFT of a (bt, n) VMEM tile: one complex GEMM with the DFT
    matrix in the form of ``_cgemm``."""
    return _cgemm(xr, xi, wr, wd, ws)


def _slab_dft(xr, xi, sr, si):
    """Natural-order DFT across the leading axis of (q, bt, 128) planes
    whose slabs arrive in bit-reversed order: iterative radix-2 DIT on the
    VPU, one vectorized butterfly per stage. Row m + k of the (q, 1, 128)
    stage twiddles ``sr``/``si`` holds W_{2m}^k."""
    q, bt, lanes = xr.shape
    m = 1
    while m < q:
        g = q // (2 * m)
        vr = xr.reshape(g, 2, m, bt, lanes)
        vi = xi.reshape(g, 2, m, bt, lanes)
        wr = sr[m:2 * m].reshape(1, m, 1, lanes)
        wi = si[m:2 * m].reshape(1, m, 1, lanes)
        tr, ti = _cmul(vr[:, 1], vi[:, 1], wr, wi)
        xr = jnp.stack([vr[:, 0] + tr, vr[:, 0] - tr], 1).reshape(xr.shape)
        xi = jnp.stack([vi[:, 0] + ti, vi[:, 0] - ti], 1).reshape(xr.shape)
        m *= 2
    return xr, xi


def _tile_dft_4step(load, sr, si, tr, ti, w2r, w2d, w2s, xsr, xsi, ysr,
                    ysi, *, rows: int = 0):
    """In-VMEM four-step DFT of a (bt, q*128) tile.

    ``load(s)`` returns the (re, im) planes of lane slab ``s`` of the tile
    (im None for a real tile). Sample i = i1*128 + i2 and bin k = o2*q + o1.
    The q slabs are gathered, in bit-reversed order, into the (q, bt, 128)
    scratch ``xsr``/``xsi``; stage 1 is the q-point DFT across them, then
    the twiddle T[o1, i2] and one (q*bt, 128) @ (128, 128) stage-2 GEMM,
    its matrix ``w2r``/``w2d``/``w2s`` in the form of ``_cgemm``.
    The result for o1 is bins o2*q + o1, stored transposed into rows o1,
    o1+q, ... of the (n, bt) scratch ``ysr``/``ysi``. ``rows`` < 128 keeps
    only bins o2 < rows (the rfft half spectrum; its tables put those bins
    first).
    """
    lanes = fft_plan.LANES
    q = tr.shape[0]
    bits = q.bit_length() - 1
    keep = rows or lanes

    def gather(c, carry):
        rev = 0
        for b in range(bits):
            rev = rev | (((c >> b) & 1) << (bits - 1 - b))
        r, i = load(pl.ds(pl.multiple_of(rev * lanes, lanes), lanes))
        xsr[c] = r
        xsi[c] = jnp.zeros_like(r) if i is None else i
        return carry

    jax.lax.fori_loop(0, q, gather, 0)
    ar, ai = _slab_dft(xsr[...], xsi[...], sr, si)
    br, bi = _cmul(ar, ai, tr, ti)
    shape = br.shape
    cr, ci = _cgemm(br.reshape(-1, lanes), bi.reshape(-1, lanes), w2r, w2d,
                    w2s)
    xsr[...] = cr.reshape(shape)
    xsi[...] = ci.reshape(shape)

    def scatter(o1, carry):
        at = pl.ds(o1, keep, stride=q)
        ysr[at, :] = xsr[o1].T[:keep]
        ysi[at, :] = xsi[o1].T[:keep]
        return carry

    jax.lax.fori_loop(0, q, scatter, 0)


def twiddle(row, col, n_global: int):
    """W_{n_global}^{row * col} for int32 index arrays (broadcast).

    The exponent is reduced exactly mod n_global (a power of two up to
    2^32) by int32 wraparound and a mask. Its two 16-bit halves convert to
    f32 exactly, so nothing is lost before the one rounding of the angle.
    Inside a kernel this is the twiddle epilogue computed in registers —
    zero HBM traffic, no table; outside one it is plain jnp.
    """
    mask = n_global - 1 if n_global <= 1 << 31 else -1
    m = (row * col) & mask
    hi = ((m >> 16) & 0xFFFF).astype(jnp.float32)
    lo = (m & 0xFFFF).astype(jnp.float32)
    frac = hi * (65536.0 / n_global) + lo * (1.0 / n_global)
    # the same angle in (-pi, pi]: cos/sin are most accurate near zero
    frac = jnp.where(frac >= 0.5, frac - 1.0, frac)
    ang = (-2.0 * math.pi) * frac
    return jnp.cos(ang), jnp.sin(ang)


def _global_twiddle(row_base, bt, n, n_global, period):
    """The twiddle of a (bt, n) tile whose first row is logical row
    ``row_base``: row r, bin k gets W_{n_global}^{g(r) * k} with
    g(r) = row_base + r, taken mod ``period`` when that is nonzero.

    ``period == 0`` is the distributed four-step's twiddle (the global row
    index); ``period == n2`` is the level-1 four-step's W_N^{i2 * o1}
    (rows (b, i2), i2 = r mod n2).
    """
    row = row_base + jax.lax.broadcasted_iota(jnp.int32, (bt, n), 0)
    if period:
        row = row & (period - 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, n), 1)
    return twiddle(row, col, n_global)


# ---------------------------------------------------------------------------
# row-major batch kernel (level 0 leaf)


def split_twiddle(global_twiddle):
    """``(n_global, row_off[, period])`` -> (n_global, row_off, period);
    (0, None, 0) when there is no on-the-fly twiddle."""
    if global_twiddle is None:
        return 0, None, 0
    g_n, row_off, *rest = global_twiddle
    return g_n, row_off, (rest[0] if rest else 0)


def _epilogue(yr, yi, row_base, twiddle_n: int, period: int):
    """Multiply the natural-order (bt, n) result by the fused twiddle, if
    there is one (``twiddle_n``)."""
    if not twiddle_n:
        return yr, yi
    tr, ti = _global_twiddle(row_base, yr.shape[0], yr.shape[1], twiddle_n,
                             period)
    return _cmul(yr, yi, tr, ti)


def _row_dft(xr_ref, xi_ref, rows, tables, scratch):
    """Natural-order DFT of the (bt, n) tile ``x_ref[rows, :]``: the direct
    GEMM, or the four-step through its scratch refs."""
    if not scratch:
        return _tile_dft_direct(xr_ref[rows, :], xi_ref[rows, :],
                                *(t[...] for t in tables))
    _tile_dft_4step(lambda s: (xr_ref[rows, s], xi_ref[rows, s]),
                    *(t[...] for t in tables), *scratch)
    ysr, ysi = scratch[2:]
    return ysr[...].T, ysi[...].T


def _matfft_kernel(*refs, n_tables: int, twiddle_n: int, period: int):
    """DFT of one (bt, n) row tile, with the optional fused twiddle."""
    xr_ref, xi_ref = refs[:2]
    tables = refs[2:2 + n_tables]
    off_ref, outr_ref, outi_ref = refs[2 + n_tables:5 + n_tables]
    scratch = refs[5 + n_tables:]
    yr, yi = _row_dft(xr_ref, xi_ref, slice(None), tables, scratch)
    row_base = off_ref[0] + pl.program_id(0) * yr.shape[0]
    outr_ref[...], outi_ref[...] = _epilogue(yr, yi, row_base, twiddle_n,
                                             period)


def _leaf_tables(n: int, table_spec):
    """Operands and BlockSpecs of the tile DFT of length n: the direct
    matrix, or the four-step's stage twiddles, (q, 1, 128) twiddle T and
    128-point DFT matrix; each DFT matrix in the form of ``_cgemm``."""
    if n <= DIRECT_N:
        tables = fft_plan.gauss_dft_matrix(n)
    else:
        q, lanes = fft_plan.leaf_split(n)
        tr, ti = fft_plan.twiddle_table(q, lanes, n)
        tables = (*fft_plan.slab_twiddles(q), tr.reshape(q, 1, lanes),
                  ti.reshape(q, 1, lanes), *fft_plan.gauss_dft_matrix(lanes))
    return ([jnp.asarray(t) for t in tables],
            [table_spec(t.shape) for t in tables])


def _scratch(n: int, bt: int, out_rows: int | None = None):
    """The four-step's (q, bt, 128) slab buffers and (out_rows, bt) reorder
    buffers, out_rows defaulting to n (none for a direct DFT)."""
    if n <= DIRECT_N:
        return []
    lanes = fft_plan.LANES
    return ([pltpu.VMEM((n // lanes, bt, lanes), jnp.float32)] * 2
            + [pltpu.VMEM((out_rows or n, bt), jnp.float32)] * 2)


def _row_offset_operand(row_off):
    """The global-twiddle row offset (0 without one) as a (1,) int32
    scalar in SMEM, and its BlockSpec."""
    off = jnp.asarray(0 if row_off is None else row_off)
    return (off.reshape(1).astype(jnp.int32),
            pl.BlockSpec(memory_space=pltpu.SMEM))


def matfft(xr: jnp.ndarray, xi: jnp.ndarray, *,
           global_twiddle: tuple | None = None,
           batch_tile: int | None = None,
           interpret: bool | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched forward DFT along the last axis of planar (rows, n) arrays.

    Args:
      xr, xi: float32 (rows, n) planes; n a power of two <= plan.MAX_LEAF.
      global_twiddle: ``(n_global, row_off[, period])`` — on-the-fly
        twiddle: output row r, bin k is multiplied by
        W_{n_global}^{g * k}, g = row_off + r (mod ``period`` if given,
        a power of two).
      batch_tile: rows per kernel instance (defaults to a VMEM-sized tile).
      interpret: Pallas interpret mode; ``None`` picks it off-TPU only.
    """
    if xr.ndim != 2:
        raise ValueError(f"matfft expects 2-D (rows, n), got {xr.shape}")
    rows, n = xr.shape
    p = fft_plan.make_plan(n)
    if p.levels != 1:
        raise ValueError(f"n={n} exceeds single-kernel capacity; use ops.fft")

    bt = min(batch_tile or default_batch_tile(n), _max_tile(n))
    g_n, row_off, period = split_twiddle(global_twiddle)
    pad = (-rows) % bt
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        xi = jnp.pad(xi, ((0, pad), (0, 0)))
    grid = (xr.shape[0] // bt,)

    row_spec = pl.BlockSpec((bt, n), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct(xr.shape, jnp.float32)] * 2
    off, off_spec = _row_offset_operand(row_off)

    def table_spec(shape):
        return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))

    tables, table_specs = _leaf_tables(n, table_spec)
    kernel = functools.partial(_matfft_kernel, n_tables=len(tables),
                               twiddle_n=g_n, period=period)
    yr, yi = _pallas(
        kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, *table_specs, off_spec],
        out_specs=[row_spec, row_spec],
        out_shape=out_shape,
        scratch_shapes=_scratch(n, bt),
        interpret=resolve_interpret(interpret),
        name=f"dft_direct_{n}" if n <= DIRECT_N else f"matfft_{n}",
    )(xr, xi, *tables, off)

    if pad:
        yr, yi = yr[:rows], yi[:rows]
    return yr, yi


# ---------------------------------------------------------------------------
# column-strided batch kernel (zero-copy four-step passes)


def _col_kernel(*refs, n_tables: int, cols: int, chunk: int, out_major: str,
                twiddle_n: int, period: int):
    """DFT of the ct columns of one (1, L, ct) block, stored row-major
    (ct, L) or back in column order.

    Direct lengths transpose and transform the whole block. Four-step
    lengths transpose it into a (ct, L) scratch one 128-bin lane slab at a
    time, then run the row tile DFT on ``chunk``-row pieces of it (the
    tile matfft uses at this length), so VMEM holds one chunk's
    intermediates, not the block's.
    """
    xr_ref, xi_ref = refs[:2]
    tables = refs[2:2 + n_tables]
    off_ref, outr_ref, outi_ref = refs[2 + n_tables:5 + n_tables]
    scratch = refs[5 + n_tables:]
    L, ct = xr_ref.shape[1:]
    # logical row of this block's first column = b*cols + j*ct
    row_base = off_ref[0] + pl.program_id(0) * cols + pl.program_id(1) * ct

    if not scratch:
        xr = xr_ref[0].T  # (L, ct) -> (ct, L): VMEM transpose, not HBM
        xi = xi_ref[0].T
        # A 1-row tile would contract on XLA's M=1 GEMV path, whose
        # accumulation order differs from the GEMM path every wider tile
        # takes. Pad to M=2 in VMEM (per-row GEMM results are independent
        # of other rows' values), so single-column slab calls stay bitwise
        # equal to the monolithic kernel — the overlapped distributed
        # pipeline's chunks=n2l edge relies on this.
        squeeze = ct == 1
        if squeeze:
            xr = jnp.concatenate([xr, jnp.zeros_like(xr)], axis=0)
            xi = jnp.concatenate([xi, jnp.zeros_like(xi)], axis=0)
        yr, yi = _tile_dft_direct(xr, xi, *(t[...] for t in tables))
        if squeeze:
            yr, yi = yr[:1], yi[:1]
        yr, yi = _epilogue(yr, yi, row_base, twiddle_n, period)
        if out_major == "row":
            outr_ref[...], outi_ref[...] = yr, yi
        else:
            outr_ref[...], outi_ref[...] = yr.T[None], yi.T[None]
        return

    xtr, xti = scratch[:2]
    lanes = fft_plan.LANES

    def lane_slabs(body):
        def step(k, carry):
            body(pl.ds(pl.multiple_of(k * lanes, lanes), lanes))
            return carry
        jax.lax.fori_loop(0, L // lanes, step, 0)

    def to_rows(s):
        xtr[:, s] = xr_ref[0, s, :].T
        xti[:, s] = xi_ref[0, s, :].T

    lane_slabs(to_rows)

    def dft_chunk(j, carry):
        r0 = pl.multiple_of(j * chunk, chunk)
        rows = pl.ds(r0, chunk)
        yr, yi = _row_dft(xtr, xti, rows, tables, scratch[2:])
        yr, yi = _epilogue(yr, yi, row_base + r0, twiddle_n, period)
        dst_r, dst_i = ((outr_ref, outi_ref) if out_major == "row"
                        else (xtr, xti))  # in place: the chunk is consumed
        dst_r[rows, :] = yr
        dst_i[rows, :] = yi
        return carry

    jax.lax.fori_loop(0, ct // chunk, dft_chunk, 0)
    if out_major == "col":
        def to_cols(s):
            outr_ref[0, s, :] = xtr[:, s].T
            outi_ref[0, s, :] = xti[:, s].T

        lane_slabs(to_cols)


def matfft_cols(xr: jnp.ndarray, xi: jnp.ndarray, *, out_major: str = "row",
                global_twiddle: tuple | None = None,
                col_tile: int | None = None, col_offset: int = 0,
                ncols: int | None = None,
                interpret: bool | None = None
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched forward DFT along the MIDDLE axis of planar (B, L, C) arrays.

    Logical batch row r = b*C + c transforms the column x[b, :, c]. The
    column-strided fetch and the transpose both happen at the BlockSpec /
    VMEM level, so no transposed copy of the operand ever exists in HBM.

    Args:
      xr, xi: float32 (B, L, C) planes; L a pow2 <= plan.MAX_LEAF, C pow2.
      out_major: "row" returns (B*ncols, L) row-major (row index b*ncols + c);
        "col" returns (B, L, ncols) with out[b, o, c] — i.e. the result is
        written back in column order, which is exactly the o2-major store
        the four-step's final reorder needs.
      global_twiddle: ``(n_global, row_off[, period])`` — on-the-fly
        twiddle for logical row ``row_off + b*ncols + c`` (mod ``period``
        if given; see matfft).
      col_tile: columns per kernel instance (defaults to a VMEM-sized tile,
        never narrower than the lane width where the slab is that wide).
      col_offset, ncols: transform only the column slab
        ``[col_offset, col_offset + ncols)``, fetched from the full operand
        by the BlockSpec index map — a per-slab call reads the big buffer
        in place instead of forcing XLA to materialize (retile) a slice.
        The overlapped distributed pipeline's pass-2 slabs use this. Both
        must be pow2-aligned (ncols pow2, col_offset a multiple of it). On
        a TPU a slab narrower than the lane width must be the whole C.
      interpret: Pallas interpret mode; ``None`` picks it off-TPU only.
    """
    if xr.ndim != 3:
        raise ValueError(f"matfft_cols expects 3-D (B, L, C), got {xr.shape}")
    B, L, C = xr.shape
    p = fft_plan.make_plan(L)
    if p.levels != 1:
        raise ValueError(f"L={L} exceeds single-kernel capacity")
    if not fft_plan.is_pow2(C):
        raise ValueError(f"column count must be a power of two, got {C}")
    if out_major not in ("row", "col"):
        raise ValueError(f"unknown out_major {out_major!r}")
    nc = C - col_offset if ncols is None else ncols
    if not fft_plan.is_pow2(nc):
        raise ValueError(f"ncols must be a power of two, got {nc}")
    if col_offset % nc or col_offset + nc > C:
        raise ValueError(
            f"column slab [{col_offset}, {col_offset + nc}) must be an "
            f"aligned pow2 slab of the {C} columns")
    interpret = resolve_interpret(interpret)
    if not interpret and nc < fft_plan.LANES and nc != C:
        raise ValueError(
            f"a {nc}-column slab of {C} columns is narrower than the "
            f"{fft_plan.LANES}-lane tile a TPU block needs")

    ct = _col_tile(L, nc, col_tile)
    chunk = min(ct, default_batch_tile(L))
    grid = (B, nc // ct)
    off_blocks = col_offset // ct  # exact: ct | nc | col_offset
    # a block this large is fetched and stored without double buffering:
    # two copies of it would not fit in VMEM beside the four-step scratch
    mode = pl.Buffered(1) if L * ct >= _SINGLE_BUFFER_ELEMS else None

    in_spec = pl.BlockSpec((1, L, ct), lambda b, j: (b, 0, j + off_blocks),
                           pipeline_mode=mode)

    g_n, row_off, period = split_twiddle(global_twiddle)
    off, off_spec = _row_offset_operand(row_off)

    if out_major == "row":
        out_shape = [jax.ShapeDtypeStruct((B * nc, L), jnp.float32)] * 2
        blocks_per_b = nc // ct
        out_spec = pl.BlockSpec((ct, L),
                                lambda b, j: (b * blocks_per_b + j, 0),
                                pipeline_mode=mode)
    else:
        out_shape = [jax.ShapeDtypeStruct((B, L, nc), jnp.float32)] * 2
        out_spec = pl.BlockSpec((1, L, ct), lambda b, j: (b, 0, j),
                                pipeline_mode=mode)

    def table_spec(shape):
        return pl.BlockSpec(shape, lambda b, j: tuple(0 for _ in shape))

    tables, table_specs = _leaf_tables(L, table_spec)
    scratch = []
    if L > DIRECT_N:
        scratch = ([pltpu.VMEM((ct, L), jnp.float32)] * 2
                   + _scratch(L, chunk))
    kernel = functools.partial(_col_kernel, n_tables=len(tables), cols=nc,
                               chunk=chunk, out_major=out_major,
                               twiddle_n=g_n, period=period)
    return _pallas(
        kernel,
        grid=grid,
        in_specs=[in_spec, in_spec, *table_specs, off_spec],
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name=f"dft_cols_{L}" if L <= DIRECT_N else f"matfft_cols_{L}",
    )(xr, xi, *tables, off)


# NOTE: the level-1 four-step that chained two matfft_cols calls
# (`four_step_zero_copy`) moved to repro/fft/executors.py, re-expressed on
# the shared `axis_pass` builder — the same primitive that powers the true
# N-D fftn/rfftn passes and the distributed pass boundaries.


# ---------------------------------------------------------------------------
# real-input fast path (rfft leaf)


def untangle_half_spectrum(yr, yi, vr, vi):
    """One-sided real-input spectrum from the half-length packed transform.

    Given Y = DFT_m(x[..., 0::2] + 1j*x[..., 1::2]) along the last axis,
    the even/odd sub-spectra are recovered from the conjugate-symmetric
    partner Y[(m-k) % m] and combined with the packing twiddle
    v[k] = W_{2m}^k:

        E[k] = (Y[k] + conj(Y[m-k]))/2      O[k] = (Y[k] - conj(Y[m-k]))/2i
        X[k] = E[k] + v[k]*O[k]   k < m;    X[m] = E[0] - O[0]  (Nyquist)

    Pure jnp on (..., m) planes -> (..., m+1): the host epilogue of the
    level-1 rfft path, where the half transform spans two kernel passes.
    """
    # conj partner p[k] = Y[(m-k) % m]: reverse then rotate right by one.
    pr = jnp.roll(yr[..., ::-1], 1, axis=-1)
    pi = jnp.roll(yi[..., ::-1], 1, axis=-1)
    er, ei = 0.5 * (yr + pr), 0.5 * (yi - pi)
    our, oui = 0.5 * (yi + pi), 0.5 * (pr - yr)
    xr = er + vr * our - vi * oui
    xi = ei + vr * oui + vi * our
    nyq = er[..., :1] - our[..., :1]
    return (jnp.concatenate([xr, nyq], axis=-1),
            jnp.concatenate([xi, jnp.zeros_like(nyq)], axis=-1))


def _rfft_kernel(*refs, n_tables: int, nyquist: bool):
    """Half a spectrum of a natural (bt, n) real tile.

    Direct (n/2 <= DIRECT_N): one real GEMM with the (n, n/2) matrix of
    plan.real_dft_matrix. Otherwise the four-step over the real samples,
    keeping bins o2 < 64 of each slab (plan.real_four_step_tables). The
    tables decide whether the n/2 bins are the one-sided spectrum or the
    packed half-length transform. ``nyquist`` appends bin n/2 =
    sum_s x[s] * (-1)^s.
    """
    x_ref = refs[0]
    tables = refs[1:1 + n_tables]
    outr_ref, outi_ref = refs[1 + n_tables:3 + n_tables]
    scratch = refs[3 + n_tables:]
    if not scratch:
        fr, fi = tables
        yr, yi = _dot(x_ref[...], fr[...]), _dot(x_ref[...], fi[...])
    else:
        _tile_dft_4step(lambda s: (x_ref[:, s], None),
                        *(t[...] for t in tables), *scratch,
                        rows=fft_plan.LANES // 2)
        yr, yi = scratch[2][...].T, scratch[3][...].T
    bt, n = x_ref.shape
    m = n // 2
    if nyquist:
        sign = jax.lax.broadcasted_iota(jnp.int32, (bt, n), 1) % 2
        alt = jnp.where(sign == 0, x_ref[...], -x_ref[...])
        outr_ref[:, :m] = yr
        outr_ref[:, m:] = jnp.sum(alt, axis=1, keepdims=True)
        outi_ref[:, :m] = yi
        outi_ref[:, m:] = jnp.zeros((bt, 1), jnp.float32)
    else:
        outr_ref[...] = yr
        outi_ref[...] = yi


def _rfft_pallas(x: jnp.ndarray, batch_tile: int | None,
                 interpret: bool | None, packed: bool, what: str
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shared plumbing behind rfft_leaf / rfft_pack_leaf (see those)."""
    if x.ndim != 2:
        raise ValueError(f"{what} expects 2-D (rows, n), got {x.shape}")
    rows, n = x.shape
    fft_plan.log2i(n)
    if n < 4:
        raise ValueError(f"{what} needs n >= 4, got {n}")
    m = n // 2
    p = fft_plan.make_plan(m)
    if p.levels != 1:
        raise ValueError(f"n={n} exceeds {what} capacity; use ops.rfft")

    bt = min(batch_tile or default_batch_tile(m), _max_tile(m))
    pad = (-rows) % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (x.shape[0] // bt,)
    width = m if packed else m + 1

    in_spec = pl.BlockSpec((bt, n), lambda i: (i, 0))
    out_spec = pl.BlockSpec((bt, width), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((x.shape[0], width), jnp.float32)] * 2

    def table_spec(shape):
        return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))

    if m <= DIRECT_N:
        tables = fft_plan.real_dft_matrix(n, packed)
        scratch, name = [], f"{what}_direct_{n}"
    else:
        q = n // fft_plan.LANES
        tr, ti, *g = fft_plan.real_four_step_tables(n, packed)
        tables = (*fft_plan.slab_twiddles(q), tr.reshape(q, 1, -1),
                  ti.reshape(q, 1, -1), *g)
        scratch, name = _scratch(n, bt, out_rows=m), f"{what}_{n}"
    yr, yi = _pallas(
        functools.partial(_rfft_kernel, n_tables=len(tables),
                          nyquist=not packed),
        grid=grid,
        in_specs=[in_spec, *(table_spec(t.shape) for t in tables)],
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
        name=name,
    )(x, *(jnp.asarray(t) for t in tables))

    if pad:
        yr, yi = yr[:rows], yi[:rows]
    return yr, yi


def rfft_leaf(x: jnp.ndarray, *, batch_tile: int | None = None,
              interpret: bool | None = None
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-sided spectrum of real (rows, n) input, n pow2 with n//2 a leaf
    length. Returns planar (rows, n//2 + 1) arrays.

    One kernel reads the real buffer and writes only the one-sided
    spectrum — about half the HBM bytes of the complex path.
    """
    return _rfft_pallas(x, batch_tile, interpret, False, "rfft")


def rfft_pack_leaf(x: jnp.ndarray, *, batch_tile: int | None = None,
                   interpret: bool | None = None
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Raw packed half spectrum of real (rows, n) input: DFT_m of
    x[:, 0::2] + i*x[:, 1::2], (rows, n//2) planar, NO untangle.

    The N-D rfftn contiguous-axis pass: the kernel still reads the natural
    real rows (no even/odd planes in HBM) but keeps the half spectrum
    pow2-wide so the remaining axes' column passes stay zero-copy; the
    untangle runs once, vectorized, after them (executors.rfftn).
    """
    return _rfft_pallas(x, batch_tile, interpret, True, "rfft_pack")
