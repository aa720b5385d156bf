"""The yardstick: published chip peaks and the nominal work of a transform.

The work counted is the transform's, not the implementation's, so a
later change that fuses, splits or moves passes cannot make it stale:

  operations  5 N log2 N for a complex transform of N points (the
              Cooley-Tukey count used by FFTW's benchmarks), summed over
              the transforms of a batch;
  bytes       one read and one write of the planar float32 operand,
              16 B per point, for each axis the transform runs along.

A roofline share is the least time the chip could take for that work,
``max(ops / peak_flops, bytes / peak_bandwidth)``, over the device time
the transform's program really took.
"""

from __future__ import annotations

import math

#: Published peaks per chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/work.py "
                       f"with their source") from None


def c2c_work(shape, batch: int = 1) -> tuple[float, float]:
    """(operations, bytes) of ``batch`` complex transforms of ``shape``."""
    points = math.prod(shape)
    if points < 2:
        raise ValueError(f"no transform of shape {shape}")
    ops = 5.0 * points * math.log2(points) * batch
    nbytes = 16.0 * points * len(shape) * batch
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, device_kind: str):
    """(least seconds, bound) for work on one chip of ``device_kind``."""
    pk = peaks(device_kind)
    t_ops = ops / pk["flops"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return max(t_ops, t_bytes), ("compute" if t_ops >= t_bytes
                                 else "memory")


def evolve_bytes(shape) -> float:
    """NPB FT's evolve: one read and one write of the planar spectrum."""
    return 16.0 * math.prod(shape)
