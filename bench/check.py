"""The comparison that decides ``correct``, and its control.

The number compared for a batch of transforms is the largest relative
L2 error of a row against the float64 reference: ``max over rows of
||got - want|| / ||want||``.

The control is a plain DFT by matrix product in the precision just below
the one the configurations state (float32 at ``Precision.HIGHEST``):
three bf16 passes, JAX's ``Precision.HIGH``. It is written out here as
exact bf16 products summed in float32 (``a_hi b_hi + a_hi b_lo + a_lo
b_hi``), so it computes the same on a TPU and on the CPU of a test run,
where XLA ignores ``precision``.
"""

from __future__ import annotations

import numpy as np


def rel_l2(got, want) -> float:
    """Max over rows (last axis) of ||got - want|| / ||want||."""
    want = np.asarray(want, np.complex128)
    got = np.asarray(got, np.complex128).reshape(want.shape)
    want = want.reshape(-1, want.shape[-1])
    got = got.reshape(want.shape)
    num = np.linalg.norm(got - want, axis=-1)
    den = np.maximum(np.linalg.norm(want, axis=-1), 1e-300)
    return float(np.max(num / den))


def _split(a):
    import jax.numpy as jnp
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def matmul_bf16x3(a, b):
    """``a @ b`` of float32 arrays as three bf16 passes (Precision.HIGH)."""
    import jax.numpy as jnp
    ah, al = _split(a)
    bh, bl = _split(b)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def dft_matrix(n: int):
    """Planar float32 (re, im) of the forward DFT matrix, made in float64."""
    k = np.arange(n)
    w = np.exp(-2j * np.pi * (np.outer(k, k) % n) / n)
    return w.real.astype(np.float32), w.imag.astype(np.float32)


def control_dft(xr, xi):
    """Forward DFT of rows of planar float32 ``(xr, xi)`` in bf16 x3."""
    import jax.numpy as jnp
    wr, wi = (jnp.asarray(a) for a in dft_matrix(xr.shape[-1]))
    yr = matmul_bf16x3(xr, wr) - matmul_bf16x3(xi, wi)
    yi = matmul_bf16x3(xr, wi) + matmul_bf16x3(xi, wr)
    return yr, yi


def verdict(checks) -> bool:
    """All compared numbers are finite and within their limits; a number
    whose limit is not set yet (None) passes nothing."""
    return all(lim is not None and np.isfinite(v) and v <= lim
               for _, v, lim in checks)


def report(checks) -> dict:
    """The result line's last key: each number beside its limit."""
    return {name: {"value": v, "limit": lim} for name, v, lim in checks}
