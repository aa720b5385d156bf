"""Cross-device (level 2) four-step FFT via shard_map + collectives.

This implements the paper's §VI future work ("paralleling an FFT across a
server cluster ... using RDMA") TPU-natively: the Hadoop cluster becomes a
mesh axis (or a flattened tuple of axes, up to the full 512-chip multi-pod
mesh), HDFS block exchange becomes an on-device collective over ICI, and
each "map task" runs the level-0/1 MXU kernels of repro/fft/executors.py
on its local shard.

Data layout (N = N1 * N2 global points, D devices, planar re/im):

  input   x[i], i = i1*N2 + i2, sharded contiguously: device d owns
          i in [d*N/D, (d+1)*N/D)  == rows i1 in [d*N1/D, ...) of (N1, N2)
  xchg #1 split i2, concat i1   -> (N1, N2/D)   full columns on-device
  pass 1  local FFT over i1 (length N1, batched N2/D)  + on-the-fly twiddle
  xchg #2 split o1, concat i2   -> (N2, N1/D)   full rows on-device
  pass 2  local FFT over i2 (length N2, batched N1/D), stored o2-major
  xchg #3 (natural_order only) split o2, concat o1 -> contiguous output
          shard, already o2-major — no transpose epilogue

Two exchange engines implement each cross-device transpose (DESIGN.md §8):

  overlap=None ("off")   one monolithic `lax.all_to_all` per exchange —
                         the measured baseline; every collective byte sits
                         exposed on the critical path.
  overlap=k (chunks)     the exchange is split into k column slabs, each
                         rotated through the mesh as D-1 direct
                         `lax.ppermute` rounds (double-buffered: slab c+1
                         is in flight while slab c — already assembled —
                         runs its local `fft_cols` + twiddle). By the last
                         round only the final slab's FFT is non-hidden, so
                         all but 1/k of the collective bytes can hide
                         behind MXU compute (`exposed_collective_bytes`).

Both engines are bitwise-identical transforms: the exchange is pure data
movement, and the per-slab kernels compute each column with exactly the
same GEMMs as the monolithic call (benchmarks/bench_distributed.py gates
on this).

Constraints: N, N1, N2 powers of two with D | N1 and D | N2 (hence N >= D^2)
— the standard constraint of transpose-based distributed FFTs, validated up
front by `repro.fft.spec` so it surfaces as a plan-time ValueError. With the
512-chip mesh the minimum distributed transform is 2^18 points. Chunked
overlap additionally needs chunks | N1/D and chunks | N2/D.

Twiddle note: W_N^{i2*o1} exponents reach N1*N2 ~ 2^40+, far beyond f32
integer precision. Since N is a power of two, `(i2 * o1) mod N` is computed
exactly in int32 wrap-around arithmetic (mod 2^32 then mask), keeping the
twiddle angles exact for any N <= 2^32 (`kernels.fft.matfft.twiddle`).

`build_distributed` is the strategy builder the `repro.fft` planner
consumes (the planner owns the single jit); `distributed_fft` remains as
the historical entry point, now a thin wrapper over the facade.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.fft import executors as fft_ex
from repro.kernels.fft import plan as fft_plan
from repro.kernels.fft.matfft import twiddle

# overlap="auto" heuristic bounds (DESIGN.md §8): below AUTO_MIN_N the
# per-round ppermute latency exceeds the compute the pipeline could hide
# (slab GEMMs can't cover a round); above RING_MAX_D the direct ring's
# D-1 rounds per slab degenerate into a latency ladder of tiny pieces.
OVERLAP_AUTO_MIN_N = 1 << 26
OVERLAP_RING_MAX_D = 64
OVERLAP_AUTO_CHUNKS = 4


@dataclass(frozen=True)
class DistPlan:
    n: int
    d: int           # number of devices along the FFT axes
    n1: int          # pass-1 transform length (columns)
    n2: int          # pass-2 transform length (rows)
    natural_order: bool = True  # False skips exchange #3 (TRANSPOSED_OUT)
    chunks: int | None = None   # ppermute pipeline slabs; None = all_to_all

    @property
    def n_exchanges(self) -> int:
        """Cross-device transposes executed: transposed-out skips #3."""
        return 3 if self.natural_order else 2

    @property
    def bytes_per_exchange_per_device(self) -> int:
        """Planar f32 payload each device moves in ONE exchange."""
        return 2 * 4 * self.n // self.d

    @property
    def per_leg_bytes_per_device(self) -> tuple:
        """Per-exchange-leg payload (uniform legs), tuner-facing — same
        shape of accounting as PencilPlan.per_leg_bytes_per_device."""
        return (self.bytes_per_exchange_per_device,) * self.n_exchanges

    @property
    def per_leg_exposed_bytes_per_device(self) -> tuple:
        """Structurally exposed (fill/drain) payload per leg."""
        return tuple(b // (self.chunks or 1)
                     for b in self.per_leg_bytes_per_device)

    @property
    def collective_bytes_per_device(self) -> int:
        """Planar f32 payload each device exchanges across the whole
        transform — n_exchanges legs, so transposed-out plans report one
        exchange fewer (previously this over-reported by one a2a)."""
        return self.n_exchanges * self.bytes_per_exchange_per_device

    @property
    def exposed_collective_bytes_per_device(self) -> int:
        """Bytes per device that CANNOT overlap compute: the pipeline's
        fill/drain slab per exchange. chunks=None (or 1) exposes every
        byte; k slabs expose 1/k of each leg. Full hiding of the rest
        additionally needs per-round compute >= per-round transfer time —
        the bench's event model accounts for that; this is the structural
        lower bound."""
        return self.collective_bytes_per_device // (self.chunks or 1)


def plan_distributed(n: int, num_devices: int, *, natural_order: bool = True,
                     chunks: int | None = None) -> DistPlan:
    p = fft_plan.log2i(n)
    pd = fft_plan.log2i(num_devices)
    if p < 2 * pd:
        raise ValueError(
            f"distributed FFT needs n >= D^2 (n=2^{p}, D=2^{pd}); "
            f"use segmented_fft for batches of smaller transforms")
    a = min(max(p // 2, pd), p - pd)  # log2(n1), clamped so D | n1 and D | n2
    return DistPlan(n=n, d=num_devices, n1=1 << a, n2=1 << (p - a),
                    natural_order=bool(natural_order), chunks=chunks)


@dataclass(frozen=True)
class PencilPlan:
    """Cross-device plan for an N-D pencil-decomposed transform.

    Input (n0, ..., n_{nd-1}) shards its leading nd-1 axes over a device
    grid (2-D: the flattened mesh, grid=(D,); 3-D: one mesh axis per
    sharded axis, grid=(d0, d1)); each device FFTs its local rows of the
    contiguous last axis, then ``ndim-1`` re-pencil exchange legs each
    re-shard one transformed axis and un-shard the next axis to transform
    — (arXiv:2202.12756's slab/pencil structure on our existing exchange
    engines). For 2-D that is the familiar ONE exchange vs three for the
    1-D distributed four-step; 3-D volumes run two legs.
    """

    shape: tuple      # (n0, ..., n_{nd-1}) global volume
    d: int            # total devices along the FFT axes
    grid: tuple = None  # devices per exchange leg k (shards axis k)
    chunks: int | None = None  # ppermute pipeline slabs; None = all_to_all

    def __post_init__(self):
        if self.grid is None:  # legacy 2-D callers: one flattened ring
            object.__setattr__(self, "grid", (self.d,))

    @property
    def n(self) -> int:
        return math.prod(self.shape)

    @property
    def n_exchanges(self) -> int:
        return len(self.shape) - 1

    @property
    def bytes_per_exchange_per_device(self) -> int:
        """Planar f32 payload each device moves in ONE exchange leg (every
        leg re-pencils the full local volume, so legs are equal-sized)."""
        return 2 * 4 * self.n // self.d

    @property
    def per_leg_bytes_per_device(self) -> tuple:
        """Per-exchange-leg payload, leg order = transform order (axis
        nd-2 first, axis 0 last) — what the tuner ranks against."""
        return (self.bytes_per_exchange_per_device,) * self.n_exchanges

    @property
    def collective_bytes_per_device(self) -> int:
        return self.n_exchanges * self.bytes_per_exchange_per_device

    @property
    def per_leg_exposed_bytes_per_device(self) -> tuple:
        """Structurally exposed (fill/drain) payload per leg."""
        return tuple(b // (self.chunks or 1)
                     for b in self.per_leg_bytes_per_device)

    @property
    def exposed_collective_bytes_per_device(self) -> int:
        """Fill/drain slab per exchange (see DistPlan's twin property)."""
        return self.collective_bytes_per_device // (self.chunks or 1)


def pencil_grid(shape, num_devices: int, axis_sizes=None) -> tuple:
    """Device-grid factors for the pencil legs of an N-D ``shape``.

    2-D pencils flatten every mesh axis into one exchange ring (grid=(D,),
    the PR-5 layout). 3-D volumes shard BOTH leading axes, one mesh axis
    each — the caller must supply the per-mesh-axis sizes (in spec.axes
    order) so the grid matches the mesh's actual structure.
    """
    nd = len(shape)
    if nd == 2:
        return (int(num_devices),)
    if axis_sizes is None:
        raise ValueError(
            f"{nd}-D pencil volumes shard the {nd - 1} leading axes over a "
            f"device grid: plan with a mesh (its axes become the grid, "
            f"e.g. a (4, 2) mesh for shape={shape})")
    grid = tuple(int(g) for g in axis_sizes)
    if len(grid) != nd - 1:
        raise ValueError(
            f"{nd}-D pencil needs exactly {nd - 1} mesh axes (one "
            f"device-grid factor per sharded leading axis of "
            f"shape={shape}); got {len(grid)} axes of sizes {grid}")
    return grid


def pencil_r2c_half(shape, grid, impl: str):
    """The packed half-width pencil shape for a real-input transform, or
    None when the flop-halved path cannot apply (tiny last axis, non-GEMM
    impl, or a final exchange leg that cannot split the half width).

    The r2c pencil rides the rfftn packing: the contiguous pass transforms
    n_last/2 packed complex points, every exchange leg moves the half
    width, and ONE N-D untangle on the global result recovers the real
    spectrum — flop- and byte-halved end to end (DESIGN.md §14).
    """
    shape = tuple(int(d) for d in shape)
    m = shape[-1] // 2
    if impl != "matfft" or shape[-1] < 4:
        return None
    half = (*shape[:-1], m)
    grid = tuple(int(g) for g in grid)
    for k, g in enumerate(grid):  # every leg must split the half volume
        if half[k] % g or half[k + 1] % g:
            return None
    return half


def plan_pencil(shape, num_devices: int, *, grid=None,
                chunks: int | None = None) -> PencilPlan:
    shape = tuple(int(d) for d in shape)
    if len(shape) < 2:
        raise ValueError(f"pencil decomposition needs >= 2 axes, "
                         f"got shape={shape}")
    fft_plan.log2i(num_devices)
    if grid is None:
        grid = pencil_grid(shape, num_devices)
    grid = tuple(int(g) for g in grid)
    if math.prod(grid) != num_devices:
        raise ValueError(
            f"pencil device grid {grid} must multiply to the device count "
            f"D={num_devices}")
    for g in grid:
        fft_plan.log2i(g)
    for k, g in enumerate(grid):
        # leg k shards axis k on input and splits axis k+1 on exchange
        if shape[k] % g or shape[k + 1] % g:
            raise ValueError(
                f"pencil decomposition needs grid[{k}]={g} to divide both "
                f"axis {k} (the input shard) and axis {k + 1} (the "
                f"exchange split) of shape={shape}")
    return PencilPlan(shape=shape, d=num_devices, grid=grid, chunks=chunks)


def _resolve_overlap_knob(n_total: int, num_devices: int, slab_widths,
                          overlap, widths_desc: str) -> int | None:
    """Shared ``overlap`` knob parser for both exchange engines.

    "off"/None -> None. "auto" -> OVERLAP_AUTO_CHUNKS when the ring
    pipeline can plausibly pay for itself (n_total >= OVERLAP_AUTO_MIN_N,
    ring size <= OVERLAP_RING_MAX_D, slabs at least 2 wide), else None.
    An explicit int is validated — chunks must divide every per-device
    slab width so each ppermute round rotates equal pieces — and is
    honoured even where "auto" would decline (user override).
    """
    if overlap is None or overlap == "off":
        return None
    min_w = min(slab_widths)
    if overlap == "auto":
        if (n_total < OVERLAP_AUTO_MIN_N
                or num_devices > OVERLAP_RING_MAX_D or min_w < 2):
            return None
        return min(OVERLAP_AUTO_CHUNKS, min_w)
    if isinstance(overlap, bool) or not isinstance(overlap, int):
        raise ValueError(
            f"overlap must be 'auto', 'off', or a chunk count (int); "
            f"got {overlap!r}")
    if overlap < 1 or any(w % overlap for w in slab_widths):
        raise ValueError(
            f"overlap={overlap} chunks must divide {widths_desc} so "
            f"every ppermute round rotates equal slabs")
    return overlap


def resolve_overlap_pencil(shape, num_devices: int, overlap, *,
                           grid=None) -> int | None:
    """Resolve the ``overlap`` knob for the pencil exchanges: chunks must
    divide every per-leg per-device slab width shape[k+1]/grid[k] (for
    2-D that is the familiar n1/D of the ONE exchange)."""
    shape = tuple(int(d) for d in shape)
    plan = plan_pencil(shape, num_devices, grid=grid)
    widths = tuple(shape[k + 1] // g for k, g in enumerate(plan.grid))
    return _resolve_overlap_knob(
        plan.n, max(plan.grid), widths, overlap,
        f"every per-leg exchange slab width "
        f"{'n1/D=%d' % widths[0] if len(widths) == 1 else widths} "
        f"(shape={shape}, grid={plan.grid})")


def resolve_overlap(n: int, num_devices: int, overlap) -> int | None:
    """Resolve the ``overlap`` knob for the 1-D engine: chunks must
    divide both per-device slab widths n1/D and n2/D."""
    if overlap is None or overlap == "off":
        return None
    plan = plan_distributed(n, num_devices)
    n1l, n2l = plan.n1 // plan.d, plan.n2 // plan.d
    return _resolve_overlap_knob(
        n, num_devices, (n1l, n2l), overlap,
        f"both per-device slab widths n1/D={n1l} and n2/D={n2l} "
        f"(n={n}, D={num_devices})")


def _axis_size(mesh: Mesh, axis_names) -> int:
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    return math.prod(mesh.shape[a] for a in axis_names)


def _zeros_planar(shape):
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))


def _ring(d: int, ax, didx, take, place, bufs):
    """One slab exchange: D-1 direct ppermute rounds + the local piece.

    Round r rotates by r — device ``didx`` sends ``take((didx+r)%D)`` and
    receives source (didx-r)%D's piece, placed by ``place``. The rounds
    carry independent data (no chained buffer), so the scheduler can run
    them concurrently with each other and with the previous slab's FFT.
    Shared by BOTH overlapped engines (1-D three-exchange and 2-D pencil).
    """
    bufs = place(bufs, take(didx), didx)
    for r in range(1, d):
        perm = [(s, (s + r) % d) for s in range(d)]
        pr, pi = take((didx + r) % d)
        rr = lax.ppermute(pr, ax, perm)
        ri = lax.ppermute(pi, ax, perm)
        bufs = place(bufs, (rr, ri), (didx - r) % d)
    return bufs


def _twiddle(i2g: jnp.ndarray, o1: jnp.ndarray, n: int):
    """Planar W_n^{i2g*o1} with exact pow2 modular exponent (see header)."""
    return twiddle(i2g.astype(jnp.int32)[:, None],
                   o1.astype(jnp.int32)[None, :], n)


def build_distributed(n: int, mesh: Mesh, axis_names=("data", "model"), *,
                      impl: str = "matfft", natural_order: bool = True,
                      fuse_twiddle: bool = False,
                      interpret: bool | None = None,
                      layout: str = "zero_copy",
                      overlap: int | None = None):
    """Build the shard_map'd cross-device four-step for a length-n signal.

    ``overlap`` is the RESOLVED chunk count (see `resolve_overlap`; the
    planner resolves "auto"). Returns the shard-mapped function over
    planar (n,) global arrays; the caller (the planner) wraps it in ONE
    `jax.jit` and caches it.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    d = _axis_size(mesh, axis_names)
    plan = plan_distributed(n, d, natural_order=natural_order,
                            chunks=overlap)
    n1, n2 = plan.n1, plan.n2
    n1l, n2l = n1 // d, n2 // d
    ax = tuple(axis_names)
    if overlap is not None and (n1l % overlap or n2l % overlap):
        raise ValueError(
            f"overlap={overlap} does not divide slab widths "
            f"n1/D={n1l}, n2/D={n2l}")

    def pass1(ar, ai, row0, rows):
        """Local pass 1 on an assembled (n1, rows) column slab whose first
        global row (i2) is ``row0``: FFT + the W_n^{i2*o1} twiddle, fused
        into the kernel epilogue when the leaf allows it."""
        can_fuse = (fuse_twiddle and impl == "matfft"
                    and fft_plan.make_plan(n1).levels == 1)
        if can_fuse:
            row_off = row0.astype(jnp.int32).reshape(1)
            return fft_ex.fft_cols(ar, ai, impl=impl, interpret=interpret,
                                   global_twiddle=(n, row_off),
                                   layout=layout)
        ar, ai = fft_ex.fft_cols(ar, ai, impl=impl, interpret=interpret,
                                 layout=layout)
        # ar: (rows, n1), row j = global i2 row0 + j, cols = o1
        i2g = row0.astype(jnp.int32) + jnp.arange(rows, dtype=jnp.int32)
        tw_r, tw_i = _twiddle(i2g, jnp.arange(n1, dtype=jnp.int32), n)
        return ar * tw_r - ai * tw_i, ar * tw_i + ai * tw_r

    def pass2(br, bi, out_major, col_offset=0, ncols=None):
        """Local pass 2 on (n2, n1l): FFT each length-n2 column. The
        o2-major ("col") store is what exchange #3 consumes directly, so
        the old `cr.T.reshape(-1)` HBM transpose epilogue is folded into
        the kernel's out_major store."""
        return fft_ex.fft_cols(br, bi, impl=impl, interpret=interpret,
                               layout=layout, out_major=out_major,
                               col_offset=col_offset, ncols=ncols)

    def local_monolithic(xr_loc, xi_loc):
        # Device-local shard: contiguous rows of the (n1, n2) matrix.
        didx = lax.axis_index(ax)

        def a2a(a):  # global transpose: split cols, concat rows
            return lax.all_to_all(a, ax, split_axis=1, concat_axis=0,
                                  tiled=True)

        # ---- xchg #1: (n1l, n2) -> (n1, n2l): full columns arrive ----
        ar = a2a(xr_loc.reshape(n1l, n2))
        ai = a2a(xi_loc.reshape(n1l, n2))

        # ---- pass 1: FFT columns (length n1), batched over n2l ----
        # fft_cols folds the local transpose into the kernel's BlockSpec:
        # with layout="zero_copy" the (n1, n2l) shard is read column-strided
        # and the (n2l, n1) result written row-major, no `.T` copy in HBM.
        br, bi = pass1(ar, ai, didx * n2l, n2l)

        # ---- xchg #2: (n2l, n1) -> (n2, n1l): full rows arrive ----
        br, bi = a2a(br), a2a(bi)

        if not natural_order:
            # ---- pass 2, row-major out: (n1l, n2) = [o1_loc, o2] ----
            cr, ci = pass2(br, bi, "row")
            return cr.reshape(-1), ci.reshape(-1)

        # ---- pass 2, o2-major out: (n2, n1l) = [o2, o1_loc] ----
        cr, ci = pass2(br, bi, "col")

        # ---- xchg #3: split o2 rows, concat o1 cols -> (n2l, n1) ----
        # the received layout IS the o2-major output shard: flatten free.
        def a2a_t(a):
            return lax.all_to_all(a, ax, split_axis=0, concat_axis=1,
                                  tiled=True)

        cr, ci = a2a_t(cr), a2a_t(ci)
        return cr.reshape(-1), ci.reshape(-1)

    def local_overlapped(xr_loc, xi_loc):
        k = overlap
        n2c, n1c = n2l // k, n1l // k
        didx = lax.axis_index(ax)
        xr2 = xr_loc.reshape(n1l, n2)
        xi2 = xi_loc.reshape(n1l, n2)
        zeros = _zeros_planar

        def ring(take, place, bufs):  # the shared rotation schedule
            return _ring(d, ax, didx, take, place, bufs)

        # ---- xchg #1 slab c: global columns didx*n2l + c-slab ----
        def take1(c):
            def take(dest):
                start = dest * n2l + c * n2c
                return (lax.dynamic_slice(xr2, (0, start), (n1l, n2c)),
                        lax.dynamic_slice(xi2, (0, start), (n1l, n2c)))
            return take

        def place1(bufs, piece, s):
            # source s owns global rows [s*n1l, (s+1)*n1l)
            return (lax.dynamic_update_slice(bufs[0], piece[0],
                                             (s * n1l, 0)),
                    lax.dynamic_update_slice(bufs[1], piece[1],
                                             (s * n1l, 0)))

        # ---- xchg #2 slab c: pass-1 rows c-slab into the (n2, n1l)
        # accumulator (row i2 = s*n2l + c*n2c + j for source s) ----
        def take2(br, bi):
            def take(dest):
                return (lax.dynamic_slice(br, (0, dest * n1l), (n2c, n1l)),
                        lax.dynamic_slice(bi, (0, dest * n1l), (n2c, n1l)))
            return take

        def place2(c):
            def place(bufs, piece, s):
                at = (s * n2l + c * n2c, 0)
                return (lax.dynamic_update_slice(bufs[0], piece[0], at),
                        lax.dynamic_update_slice(bufs[1], piece[1], at))
            return place

        # Software pipeline over slabs (double buffer): slab c+1's rounds
        # are issued before slab c's FFT, so its transfers have a full
        # kernel's worth of compute to hide behind; slab c's pass-1 output
        # immediately feeds its xchg #2 rounds, which hide behind slab
        # c+1's FFT. Only slab 0's arrival and the final slab's FFT are
        # structurally exposed.
        arrived = [None] * k
        arrived[0] = ring(take1(0), place1, zeros((n1, n2c)))
        acc2 = zeros((n2, n1l))
        for c in range(k):
            if c + 1 < k:
                arrived[c + 1] = ring(take1(c + 1), place1,
                                      zeros((n1, n2c)))
            br, bi = pass1(*arrived[c], didx * n2l + c * n2c, n2c)
            acc2 = ring(take2(br, bi), place2(c), acc2)
        a2r, a2i = acc2

        if not natural_order:
            cr, ci = pass2(a2r, a2i, "row")
            return cr.reshape(-1), ci.reshape(-1)

        # ---- pass 2 slab j (columns j-slab of (n2, n1l), read in place
        # via the kernel's col_offset — no retile) + xchg #3 slab j ----
        def take3(cr, ci):
            def take(dest):
                return (lax.dynamic_slice(cr, (dest * n2l, 0), (n2l, n1c)),
                        lax.dynamic_slice(ci, (dest * n2l, 0), (n2l, n1c)))
            return take

        def place3(j):
            def place(bufs, piece, s):
                at = (0, s * n1l + j * n1c)
                return (lax.dynamic_update_slice(bufs[0], piece[0], at),
                        lax.dynamic_update_slice(bufs[1], piece[1], at))
            return place

        out = zeros((n2l, n1))
        for j in range(k):
            cr, ci = pass2(a2r, a2i, "col", col_offset=j * n1c, ncols=n1c)
            out = ring(take3(cr, ci), place3(j), out)
        outr, outi = out
        return outr.reshape(-1), outi.reshape(-1)

    local = local_monolithic if overlap is None else local_overlapped
    spec = P(ax)
    # check_vma=False: pallas_call out_shapes do not carry vma metadata.
    return compat.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                            out_specs=(spec, spec), check_vma=False)


def _pencil_groups(shape, mesh: Mesh, axis_names):
    """Mesh-axis group per exchange leg + the resulting device grid.

    2-D: every mesh axis flattens into ONE exchange ring (PR-5 layout).
    3-D: exactly one mesh axis per sharded leading axis — leg k rotates
    over its own sub-ring while the other grid axis stays put, so the two
    legs' collectives are independent D_k-way transposes.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    names = tuple(axis_names)
    nd = len(shape)
    if nd == 2:
        groups = (names,)
    else:
        if len(names) != nd - 1:
            raise ValueError(
                f"{nd}-D pencil needs exactly {nd - 1} mesh axes (one "
                f"device-grid axis per sharded leading axis of "
                f"shape={tuple(shape)}); got axes {names}")
        groups = tuple((a,) for a in names)
    grid = tuple(_axis_size(mesh, g) for g in groups)
    return groups, grid


def _pencil_legs(shape, grid, groups, *, impl, interpret, layout,
                 batch_tile, overlap):
    """Build the exchange-legs closure shared by the c2c and r2c pencils.

    Input: device-local planar arrays of shape ``loc0`` = per-axis
    ``shape[i]/grid[i]`` for the sharded leading axes, full last axis —
    already transformed along the contiguous axis by the caller. Runs
    legs k = nd-2 .. 0 (exactly local fftn's axis order, so the composed
    transform is bitwise-equal to the local oracle): exchange leg k
    re-shards transformed axis k+1 over grid[k] and assembles full axis
    k, then the axis-k pass runs on the shared axis-pass kernel with a
    column-major store. Each leg uses the monolithic all_to_all or the
    chunked ppermute ring (both bitwise-identical: the slab kernels issue
    exactly the monolithic GEMMs via col_offset/ncols).
    """
    shape = tuple(int(x) for x in shape)
    nd = len(shape)
    loc0 = tuple(shape[i] // grid[i] for i in range(nd - 1)) + (shape[-1],)

    def axis_k_pass(ar, ai, S, k, col_offset=0, ncols=None):
        """Transform axis k of the local planar volume S via the shared
        axis-pass primitive ((B, L, C) view, col-major store), reshaped
        back to volume form (a slab pass narrows axis k+1 to the slab)."""
        B, L, C = math.prod(S[:k]), S[k], math.prod(S[k + 1:])
        nc = C - col_offset if ncols is None else ncols
        yr, yi = fft_ex.axis_pass(ar, ai, (B, L, C), out_major="col",
                                  impl=impl, interpret=interpret,
                                  col_tile=batch_tile, layout=layout,
                                  col_offset=col_offset, ncols=nc)
        rest = math.prod(S[k + 2:])
        out_shape = (*S[:k], L, nc // rest, *S[k + 2:])
        return yr.reshape(out_shape), yi.reshape(out_shape)

    def monolithic_leg(ar, ai, S, k):
        g = groups[k]

        def a2a(a):  # re-pencil: split transformed axis k+1, concat axis k
            return lax.all_to_all(a, g, split_axis=k + 1, concat_axis=k,
                                  tiled=True)

        ar, ai = a2a(ar), a2a(ai)
        S = list(S)
        S[k + 1] //= grid[k]
        S[k] *= grid[k]
        S = tuple(S)
        ar, ai = axis_k_pass(ar, ai, S, k)
        return ar, ai, S

    def overlapped_leg(ar, ai, S, k):
        kc = overlap
        dk, g = grid[k], groups[k]
        didx = lax.axis_index(g)
        w = shape[k + 1] // dk      # per-dest slab width on axis k+1
        wc = w // kc
        accS = list(S)
        accS[k] = S[k] * dk         # full transformed axis k assembles
        accS[k + 1] = w
        accS = tuple(accS)
        rest = math.prod(accS[k + 2:])

        def ring(take, place, bufs):  # the shared rotation schedule
            return _ring(dk, g, didx, take, place, bufs)

        # xchg slab c: sub-ring member ``dest``'s global axis-(k+1)
        # columns [dest*w + c*wc, ... + wc) of this leg's input
        def take(c):
            def take_(dest):
                start = [0] * nd
                start[k + 1] = dest * w + c * wc
                sizes = list(S)
                sizes[k + 1] = wc
                return (lax.dynamic_slice(ar, tuple(start), tuple(sizes)),
                        lax.dynamic_slice(ai, tuple(start), tuple(sizes)))
            return take_

        def place(c):
            def place_(bufs, piece, s):
                # source s owns axis-k block [s*S[k], (s+1)*S[k])
                at = [0] * nd
                at[k] = s * S[k]
                at[k + 1] = c * wc
                at = tuple(at)
                return (lax.dynamic_update_slice(bufs[0], piece[0], at),
                        lax.dynamic_update_slice(bufs[1], piece[1], at))
            return place_

        # Software pipeline (double buffer): slab c+1's ppermute rounds
        # are issued before slab c's axis pass, so the transfer has a
        # full kernel's worth of MXU compute to hide behind. The pass
        # reads the accumulator SNAPSHOT taken before ring c+1 merges
        # in (slab c's columns are already final there) — reading the
        # merged value instead would add a ring(c+1) -> fft(c) dataflow
        # edge and re-expose one slab per exchange. The kernel fetches
        # only the slab's columns via its col_offset BlockSpec, so every
        # slab issues exactly the monolithic GEMMs (bitwise-gated).
        acc = ring(take(0), place(0), _zeros_planar(accS))
        out = _zeros_planar(accS)
        for c in range(kc):
            cur = acc
            if c + 1 < kc:
                acc = ring(take(c + 1), place(c + 1), acc)
            cr, ci = axis_k_pass(cur[0], cur[1], accS, k,
                                 col_offset=c * wc * rest,
                                 ncols=wc * rest)
            at = [0] * nd
            at[k + 1] = c * wc
            out = (lax.dynamic_update_slice(out[0], cr, tuple(at)),
                   lax.dynamic_update_slice(out[1], ci, tuple(at)))
        return out[0], out[1], accS

    leg = monolithic_leg if overlap is None else overlapped_leg

    def legs(ar, ai):
        S = loc0
        for k in range(nd - 2, -1, -1):
            ar, ai, S = leg(ar, ai, S, k)
        return ar, ai

    return legs, loc0


def build_pencil(shape, mesh: Mesh, axis_names=("data", "model"), *,
                 impl: str = "matfft", interpret: bool | None = None,
                 layout: str = "zero_copy", batch_tile: int | None = None,
                 overlap: int | None = None):
    """Build the shard_map'd N-D pencil transform for an (n0, .., nk) volume.

    Data layout (device grid per `_pencil_groups`, planar re/im):

      input   leading axes sharded over the grid (2-D: rows over D; 3-D:
              axis 0 over d0, axis 1 over d1), last axis contiguous
      pass    local FFT of each row (contiguous axis, level 0/1 kernels)
      legs    ndim-1 re-pencil exchanges, axis nd-2 down to axis 0: each
              leg re-shards the just-transformed axis and assembles the
              next, then FFTs it via the shared axis-pass kernel with a
              column-major store (all_to_all or the chunked ppermute ring)

    The output is the full natural-order N-D spectrum with the SAME grid
    rotated one axis right (out_specs P(None, *groups)) — the standard
    pencil re-distribution. Both exchange engines are bitwise-identical
    transforms, same as the 1-D engines, and the leg order matches local
    `fftn` exactly so the composed result is bitwise vs the local oracle.

    ``overlap`` is the RESOLVED chunk count (`resolve_overlap_pencil`).
    Returns the shard-mapped function over planar global volumes; the
    caller (the planner) wraps it in ONE `jax.jit` and caches it.
    """
    shape = tuple(int(x) for x in shape)
    groups, grid = _pencil_groups(shape, mesh, axis_names)
    d = math.prod(grid)
    plan_pencil(shape, d, grid=grid, chunks=overlap)  # validate
    if overlap is not None:
        widths = [shape[k + 1] // grid[k] for k in range(len(shape) - 1)]
        if any(w % overlap for w in widths):
            raise ValueError(
                f"overlap={overlap} does not divide every exchange slab "
                f"width {widths} (shape={shape}, grid={grid})")
    legs, _ = _pencil_legs(shape, grid, groups, impl=impl,
                           interpret=interpret, layout=layout,
                           batch_tile=batch_tile, overlap=overlap)

    def local(xr_loc, xi_loc):
        # contiguous-axis pass on the local shard (leading axes = batch)
        ar, ai = fft_ex.fft(xr_loc, xi_loc, impl=impl, interpret=interpret,
                            batch_tile=batch_tile, layout=layout)
        return legs(ar, ai)

    in_spec = P(*groups, None)    # leading axes sharded over the grid
    out_spec = P(None, *groups)   # grid rotated one axis right
    # check_vma=False: pallas_call out_shapes do not carry vma metadata.
    return compat.shard_map(local, mesh=mesh, in_specs=(in_spec, in_spec),
                            out_specs=(out_spec, out_spec), check_vma=False)


def build_pencil_r2c(shape, mesh: Mesh, axis_names=("data", "model"), *,
                     impl: str = "matfft", interpret: bool | None = None,
                     layout: str = "zero_copy",
                     batch_tile: int | None = None,
                     overlap: int | None = None):
    """Flop-halved real-input pencil: the rfftn packing, distributed.

    The local contiguous pass consumes each real row as n_last/2 packed
    complex points (`executors.rfft_pack_pass` — literally the same
    kernels as the local rfftn fast path), then the SAME exchange legs as
    `build_pencil` run on the half-width volume, halving every leg's
    collective bytes and every axis pass's GEMMs. The result is the RAW
    packed half spectrum, grid-rotated like the c2c pencil; the caller
    (the planner) applies the ONE N-D untangle on the global array —
    outside the shard_map, exactly where local rfftn applies it, so the
    composed transform is bitwise-equal to the local `rfftn` oracle.

    Only valid when `pencil_r2c_half(shape, grid, impl)` is non-None;
    ``overlap`` is resolved against the HALF shape. Returns the
    shard-mapped function real (n0, .., n_last) -> planar half volumes.
    """
    shape = tuple(int(x) for x in shape)
    groups, grid = _pencil_groups(shape, mesh, axis_names)
    d = math.prod(grid)
    half = pencil_r2c_half(shape, grid, impl)
    if half is None:
        raise ValueError(
            f"no flop-halved r2c pencil for shape={shape}, grid={grid}, "
            f"impl={impl!r} (see pencil_r2c_half)")
    plan_pencil(half, d, grid=grid, chunks=overlap)  # validate
    legs, loc0 = _pencil_legs(half, grid, groups, impl=impl,
                              interpret=interpret, layout=layout,
                              batch_tile=batch_tile, overlap=overlap)
    n_last = shape[-1]

    def local(x_loc):
        rows2 = math.prod(loc0[:-1])
        zr, zi = fft_ex.rfft_pack_pass(
            x_loc.reshape(rows2, n_last), n_last, impl=impl,
            interpret=interpret, batch_tile=batch_tile, layout=layout)
        return legs(zr.reshape(loc0), zi.reshape(loc0))

    in_spec = P(*groups, None)
    out_spec = P(None, *groups)
    # check_vma=False: pallas_call out_shapes do not carry vma metadata.
    return compat.shard_map(local, mesh=mesh, in_specs=(in_spec,),
                            out_specs=(out_spec, out_spec), check_vma=False)


def distributed_fft(xr: jnp.ndarray, xi: jnp.ndarray, mesh: Mesh,
                    axis_names=("data", "model"), *, impl: str = "matfft",
                    natural_order: bool = True, fuse_twiddle: bool = False,
                    interpret: bool | None = None,
                    layout: str = "zero_copy", overlap="auto"):
    """Forward FFT of a single length-n planar signal sharded over ``mesh``.

    Args:
      xr, xi: (n,) float32 planes (global arrays; pjit/shard_map shards them
        along the flattened ``axis_names``).
      natural_order: if False, skip exchange #3 and return the transform
        in transposed (o1-major) block order — FFTW's TRANSPOSED_OUT, useful
        when a subsequent pointwise op + inverse FFT follows (convolution).
      layout: "zero_copy" folds the local `.T` at each pass boundary into
        the column-strided Pallas kernel (fft_cols) — the exchange already
        did the cross-device transpose, so no device-local transposed copy
        is materialized either; "copy" keeps the legacy materialized
        transposes (measured baseline).
      overlap: "auto" | "off" | int chunk count — "off" keeps the three
        monolithic all_to_alls; a chunk count pipelines each exchange as
        ppermute slab rounds hidden behind the local FFTs (DESIGN.md §8).
    Returns planar (n,) arrays, sharded like the input.

    Thin wrapper over `repro.fft.plan(placement="distributed")`: repeat
    calls with the same spec hit the plan cache and reuse the compiled
    callable.
    """
    import repro.fft as fft_api
    p = fft_api.plan(kind="c2c", n=xr.shape[-1], batch_shape=(), mesh=mesh,
                     placement="distributed", axes=axis_names, impl=impl,
                     natural_order=natural_order, fuse_twiddle=fuse_twiddle,
                     interpret=interpret, layout=layout, overlap=overlap)
    return p.execute(xr, xi)


def distributed_ifft(xr, xi, mesh, axis_names=("data", "model"), **kw):
    """Inverse FFT, sharded like distributed_fft.

    Routes through the cached plan's `execute_inverse` (the conjugation
    identity lives inside the plan's own jit), so an inverse call is ONE
    facade round-trip instead of re-entering `distributed_fft` with
    negated planes and paying plan resolution + dispatch twice.

    Behavior change vs the pre-facade wrapper: `natural_order=False` now
    fails fast with NotImplementedError (execute_inverse's plan-level
    rule) instead of silently returning the inverse in transposed block
    order — the old behavior inverted a round-tripped TRANSPOSED_OUT
    spectrum incorrectly, since the conjugation identity needs the
    forward's natural output order. Plan the inverse leg with
    natural_order=True.
    """
    import repro.fft as fft_api
    p = fft_api.plan(kind="c2c", n=xr.shape[-1], batch_shape=(), mesh=mesh,
                     placement="distributed", axes=axis_names, **kw)
    return p.execute_inverse(xr, xi)
