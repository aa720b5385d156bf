"""Plain reference of NPB FT: points of the evolved field, in float64.

NPB FT (NAS Parallel Benchmarks 3.x) transforms a complex field x once,
then each iteration t multiplies the spectrum by
``g_t(k) = exp(-4 pi^2 alpha t |k|^2)`` (``k`` the signed frequency index
of each axis, ``-n/2 <= k < n/2``) and takes the inverse transform,
unnormalized:

    u_t[p] = sum_k DFT(x)[k] g_t(k) exp(+2 pi i k.p / n)
           = sum_m x[m] G_z(p_z - m_z) G_y(p_y - m_y) G_x(p_x - m_x)

with ``G_a(d) = sum_k g_t(k) exp(2 pi i k d / n_a)`` (indices mod n_a),
real and even. This module computes the second form at a few points from
x alone, with no transform: one matrix product contracts x along its
contiguous axis for every (point, t) at once, in row blocks, then two
contractions finish the other axes. The array axes are (z, y, x),
x contiguous. Nothing of the program is imported.

``control_points`` is the same computation with float32 operands and
every product taken as three bf16 products summed in float32
(``Precision.HIGH``), the precision just below the one the configuration
states.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROWS = 1 << 11   # rows of x contracted per task


def signed_index(n: int) -> np.ndarray:
    i = np.arange(n)
    return np.where(i < n // 2, i, i - n)


def axis_kernel(n: int, alpha: float, t: int) -> np.ndarray:
    """G(d) for d = 0..n-1, float64."""
    g = np.exp(-4.0 * np.pi ** 2 * alpha * t
               * signed_index(n).astype(np.float64) ** 2)
    return (np.fft.ifft(g) * n).real


def checksum_points(shape, count: int = 1024) -> np.ndarray:
    """NPB FT's checksum points as (z, y, x) rows: j = 1..count gives
    x = j mod nx, y = 3j mod ny, z = 5j mod nz."""
    nz, ny, nx = shape
    j = np.arange(1, count + 1)
    return np.stack([(5 * j) % nz, (3 * j) % ny, j % nx], axis=1)


def _points(xr, xi, ts, alpha, pts, product):
    """``product(spec, a, b)`` is ``einsum(spec, a, b)`` in the precision
    wanted; x is contracted along x in row blocks on all cores, then the
    (z, y) plane of every (t, point) column is reduced at once."""
    nz, ny, nx = xr.shape
    pts = np.asarray(pts)
    cols = np.stack([axis_kernel(nx, alpha, t)[(pts[:, 2][None, :]
                                                 - np.arange(nx)[:, None])
                                                % nx]
                     for t in ts], axis=1).reshape(nx, -1)
    k = cols.shape[1]
    rows = nz * ny
    planes = [np.empty((rows, k)) for _ in range(2)]

    def block(r0):
        blk = slice(r0, min(r0 + ROWS, rows))
        for x, out in zip((xr, xi), planes):
            out[blk] = product("rx,xk->rk", x.reshape(rows, nx)[blk], cols)

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        list(pool.map(block, range(0, rows, ROWS)))
    vy = np.stack([axis_kernel(ny, alpha, t)[(p[1] - np.arange(ny)) % ny]
                   for t in ts for p in pts])
    vz = np.stack([axis_kernel(nz, alpha, t)[(p[0] - np.arange(nz)) % nz]
                   for t in ts for p in pts])
    re, im = (product("zk,kz->k",
                      product("zyk,ky->zk", c.reshape(nz, ny, k), vy), vz)
              for c in planes)
    return (re + 1j * im).reshape(len(ts), len(pts))


def _exact(spec, a, b):
    return np.einsum(spec, np.asarray(a, np.float64),
                     np.asarray(b, np.float64), optimize=True)


def points(xr, xi, ts, alpha: float, pts) -> np.ndarray:
    """u_t at each (z, y, x) row of ``pts`` for each t of ``ts``:
    complex128 of shape (len(ts), len(pts))."""
    return _points(xr, xi, ts, alpha, pts, _exact)


def _bf16x3(spec, a, b):
    from ml_dtypes import bfloat16

    def split(v):
        v = np.asarray(v, np.float32)
        hi = v.astype(bfloat16).astype(np.float32)
        return hi, (v - hi).astype(bfloat16).astype(np.float32)

    (ah, al), (bh, bl) = split(a), split(b)
    return (np.einsum(spec, ah, bh, optimize=True)
            + (np.einsum(spec, ah, bl, optimize=True)
               + np.einsum(spec, al, bh, optimize=True)))


def control_points(xr, xi, ts, alpha: float, pts) -> np.ndarray:
    """``points`` with float32 operands and bf16 x3 products."""
    return _points(xr, xi, ts, alpha, pts, _bf16x3)
