"""Radix-2 Stockham autosort Pallas kernel (VPU comparison variant).

This is the literal port of what CUFFT-style libraries run on CUDA cores:
log2(n) butterfly stages, no bit-reversal (Stockham's ping-pong reindexing
keeps outputs in natural order). On TPU these butterflies execute on the
VPU at ~4 TFLOP/s — the matmul formulation in matfft.py beats it by moving
the work onto the MXU, and keeping both lets the benchmark harness measure
that adaptation decision instead of asserting it (see EXPERIMENTS.md §Perf).

Per-stage twiddles arrive packed in a single (n,) planar pair (see
plan.stockham_twiddles); stage s slices its l = n >> (s+1) factors at a
static offset, so the whole stage loop unrolls with static shapes.

NOTE on layout: the (bt, 2, l, m) reshapes with small m split the lane
axis into pieces narrower than 128, which Mosaic does not lower. So this
kernel runs in interpret mode only, as the CPU baseline; on a TPU it
raises rather than run interpreted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fft import plan as fft_plan
from repro.kernels.fft.matfft import resolve_interpret


def _stockham_kernel(xr_ref, xi_ref, twr_ref, twi_ref, outr_ref, outi_ref,
                     *, n: int):
    bt = xr_ref.shape[0]
    xr = xr_ref[...]
    xi = xi_ref[...]
    twr = twr_ref[...].reshape(-1)
    twi = twi_ref[...].reshape(-1)

    for off, l, m in fft_plan.stockham_stage_offsets(n):
        # x viewed as [b, h, j, k] with flat index h*l*m + j*m + k, h in {0,1}
        xr4 = xr.reshape(bt, 2, l, m)
        xi4 = xi.reshape(bt, 2, l, m)
        ar, ai = xr4[:, 0], xi4[:, 0]
        br, bi = xr4[:, 1], xi4[:, 1]
        wr = twr[off:off + l].reshape(1, l, 1)
        wi = twi[off:off + l].reshape(1, l, 1)
        # DIF butterfly: y0 = a + b ; y1 = (a - b) * w
        dr, di = ar - br, ai - bi
        tr = wr * dr - wi * di
        ti = wr * di + wi * dr
        # y[b, j, t, k] at flat index j*2m + t*m + k
        xr = jnp.stack([ar + br, tr], axis=2).reshape(bt, n)
        xi = jnp.stack([ai + bi, ti], axis=2).reshape(bt, n)

    outr_ref[...] = xr
    outi_ref[...] = xi


def stockham_fft(xr: jnp.ndarray, xi: jnp.ndarray, *,
                 batch_tile: int | None = None,
                 interpret: bool | None = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched forward DFT along the last axis via radix-2 Stockham stages.

    Interpret mode only (``interpret=None`` resolves to it off-TPU): the
    stage reshapes do not lower on a TPU, so a compiled call raises.
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "the stockham kernel does not lower on a TPU; use impl='matfft'")
    if xr.ndim != 2:
        raise ValueError(f"stockham_fft expects 2-D (rows, n), got {xr.shape}")
    rows, n = xr.shape
    fft_plan.log2i(n)  # validates pow2
    if n > fft_plan.MAX_LEAF:
        raise ValueError(f"n={n} exceeds single-kernel capacity; use ops.fft")
    if n == 1:
        return xr, xi

    bt = batch_tile or max(8, min(256, (1 << 17) // n))
    pad = (-rows) % bt
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        xi = jnp.pad(xi, ((0, pad), (0, 0)))
    grid = (xr.shape[0] // bt,)

    twr, twi = (jnp.asarray(a) for a in fft_plan.stockham_twiddles(n))
    row_spec = pl.BlockSpec((bt, n), lambda i: (i, 0))
    tw_spec = pl.BlockSpec((n,), lambda i: (0,))

    yr, yi = pl.pallas_call(
        lambda *refs: _stockham_kernel(*refs, n=n),
        grid=grid,
        in_specs=[row_spec, row_spec, tw_spec, tw_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(xr.shape, jnp.float32)] * 2,
        interpret=interpret,
        name=f"stockham_{n}",
    )(xr, xi, twr, twi)

    if pad:
        yr, yi = yr[:rows], yi[:rows]
    return yr, yi
