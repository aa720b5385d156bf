"""Collective-traffic accounting from compiled (post-SPMD) HLO text.

cost_analysis() has no collective term, so we parse the optimized module:
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction's shapes are summed into per-device byte
counts, with the standard ring-algorithm multipliers:

    all-gather          (N-1)/N * result_bytes received per device
    reduce-scatter      (N-1)/N * operand_bytes
    all-reduce          2*(N-1)/N * operand_bytes   (RS + AG phases)
    all-to-all          (N-1)/N * operand_bytes
    collective-permute  operand_bytes

N (the group size) is parsed from replica_groups when present; the
conservative N->inf multiplier 1 (or 2) is used otherwise. This module
imports no jax — safe to use from benchmarks without touching device state.
"""

from __future__ import annotations

import re
from collections import defaultdict


def cost_analysis_dict(raw) -> dict:
    """``compiled.cost_analysis()`` as a flat ``{"flops": ...,
    "bytes accessed": ...}`` mapping (empty when the backend gives none)."""
    return dict(raw or {})


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
# e.g.:  %ag = bf16[64,1024]{1,0} all-gather(%x), ... replica_groups=...
# Result may be a long tuple with /*index=N*/ comments (the tuple form of
# all-to-all), hence the permissive lazy capture up to the op name.
_INSTR_RE = re.compile(
    r"=\s*(.*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int | None:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS2_RE.search(line)
    if m:  # iota format [num_groups,group_size]
        return int(m.group(2))
    return None


def collective_stats(hlo_text: str) -> dict:
    """Per-device collective byte totals from optimized HLO text."""
    out = {k: {"count": 0, "bytes": 0.0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue  # count start/done pairs once (at -start)
        result_shape, kind = m.group(1), m.group(2)
        n = _group_size(line)
        frac = (n - 1) / n if n else 1.0
        rb = _shape_bytes(result_shape)
        if kind == "all-gather":
            b = frac * rb                      # result is the gathered shape
        elif kind == "all-reduce":
            b = 2.0 * frac * rb                # ring RS + AG phases
        elif kind == "reduce-scatter":
            b = (n - 1) * rb if n else rb      # result is input/N
        elif kind == "all-to-all":
            b = frac * rb                      # result size == operand size
        else:  # collective-permute
            b = rb
        out[kind]["count"] += 1
        out[kind]["bytes"] += b
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out
