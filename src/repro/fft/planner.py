"""`repro.fft.plan` — cached executable plans (the `cufftPlanMany` analogue).

The paper builds one batched CUFFT plan per block size and reuses it across
every 512 MB map task; this module is the TPU translation. `plan(...)`
resolves the full strategy up front (spec.py), then returns a frozen
`ExecutablePlan` from a process-level cache keyed on the resolved spec +
mesh — so the jit'd callable and twiddle tables behind a given spec are
built exactly once, and repeat `execute` calls on the same spec trigger
zero retraces (`plan.trace_count` stays at 1; asserted in
tests/test_fft_plan_api.py and reported by benchmarks/bench_fft.py).

An `ExecutablePlan` carries:

  * the resolved `FftSpec` and the level-0/1 factorization (`plan.leaf`)
    plus, for distributed placement, the cross-device `DistPlan`;
  * the analytic cost model: `flops`, `gemm_macs`, `hbm_bytes` (folding the
    roofline byte counters `fft_hbm_bytes`/`rfft_hbm_bytes`), and
    `collective_bytes` for the distributed all_to_alls;
  * `execute(xr, xi)` / `execute_real(x)` / `execute_inverse(...)`,
    backed by lazily-built, id-stable jit'd callables. When called under an
    outer trace (e.g. from a deprecated `ops.*` shim inside `jax.jit`) the
    raw function is inlined instead, so plans stay transparent to jaxpr
    inspection and to the caller's own compilation cache.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp

from repro import spans
from repro.fft import executors
from repro.fft import spec as spec_mod
from repro.fft.spec import FftSpec
from repro.kernels.fft import plan as kplan
from repro.kernels.fft.matfft import resolve_interpret

_F32 = 4  # bytes per planar float32 element

_PLAN_CACHE: dict = {}
# wisdom_hits counts tuner wisdom-file lookups that skipped measurement
# (tuner.py). A wisdom hit that still BUILDS a new ExecutablePlan is a
# plan-cache miss — the two counters answer different questions ("did we
# re-measure?" vs "did we re-trace?") and are never conflated.
_CACHE_INFO = {"hits": 0, "misses": 0, "invalidations": 0,
               "wisdom_hits": 0}
# map-only jobs plan() from ThreadPoolExecutor workers (core/pipeline):
# the check-then-act on the cache must be atomic or the first same-shaped
# blocks each build (and later compile) their own plan
_CACHE_LOCK = threading.Lock()


def _is_tracer(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


class ExecutablePlan:
    """Frozen plan: resolved strategy + cost model + cached executables.

    Construct via `repro.fft.plan(...)`, never directly — the module-level
    cache is what makes repeat plans free.
    """

    def __init__(self, spec: FftSpec, mesh):
        object.__setattr__(self, "_frozen", False)
        self.spec = spec
        self.mesh = mesh
        # RLock: _build_inverse runs under it and re-enters via _forward()
        self._build_lock = threading.RLock()
        # r2c fast path packs n reals as n/2 complex on the contiguous
        # axis (DESIGN.md §4; deferred N-D untangle for ndim > 1)
        self._fast_r2c = (spec.kind == "r2c" and spec.impl == "matfft"
                          and spec.shape[-1] >= 4
                          and spec.placement != "distributed")
        # flop-halved distributed r2c: the packed half-width pencil
        # (DESIGN.md §14); set below when the grid admits it
        self._fast_r2c_pencil = False
        #: cross-device plan (distributed placement only)
        self.dist = None
        if spec.placement == "distributed":
            num_devices = math.prod(mesh.shape[a] for a in spec.axes)
            chunks = None if spec.overlap == "off" else spec.overlap
            if spec.ndim == 1:
                from repro.core.fft.distributed import plan_distributed
                self.dist = plan_distributed(
                    spec.n, num_devices, natural_order=spec.natural_order,
                    chunks=chunks)
                # the local factorization covers the longest per-device
                # pass — global n can exceed MAX_LEAF**2, each pass can't
                local_n = max(self.dist.n1, self.dist.n2)
            else:
                from repro.core.fft.distributed import (pencil_grid,
                                                        pencil_r2c_half,
                                                        plan_pencil)
                axis_sizes = tuple(mesh.shape[a] for a in spec.axes)
                grid = pencil_grid(spec.shape, num_devices, axis_sizes)
                eff_shape = spec.shape
                if spec.kind == "r2c":
                    half = pencil_r2c_half(spec.shape, grid, spec.impl)
                    if half is not None:
                        self._fast_r2c_pencil = True
                        eff_shape = half
                self.dist = plan_pencil(eff_shape, num_devices, grid=grid,
                                        chunks=chunks)
                local_n = max(eff_shape)
        elif spec.ndim == 1:
            local_n = spec.n // 2 if self._fast_r2c else spec.n
        else:
            # contiguous axis dominates; halved by the r2c packing
            last = spec.shape[-1] // 2 if self._fast_r2c else spec.shape[-1]
            local_n = max(last, *spec.shape[:-1])
        #: level-0/1 factorization of the longest per-device axis pass
        self.leaf = kplan.make_plan(max(local_n, 1))
        self._traces = {"forward": 0, "inverse": 0}
        self._fwd = None  # (inner, jitted), built lazily
        self._fwd_donated = None  # donate-argnums variant (execute_async)
        self._fwd_shardings = None  # (in, out) captured for donated builds
        self._inv = None
        object.__setattr__(self, "_frozen", True)

    def __setattr__(self, name, value):
        if getattr(self, "_frozen", False) and not name.startswith("_"):
            raise AttributeError(
                f"ExecutablePlan is frozen; cannot set {name!r}")
        object.__setattr__(self, name, value)

    def __repr__(self):
        s = self.spec
        return (f"ExecutablePlan(kind={s.kind!r}, shape={s.shape}, "
                f"batch_shape={s.batch_shape}, placement={s.placement!r}, "
                f"layout={s.layout!r}, impl={s.impl!r}, "
                f"levels={self.leaf.levels}, "
                f"fused_untangle={self.fused_untangle})")

    # ------------------------------------------------------------------
    # resolved-strategy views

    @property
    def kind(self) -> str:
        return self.spec.kind

    @property
    def n(self) -> int:
        """Total transform points (the length, for 1-D specs)."""
        return self.spec.n

    @property
    def shape(self) -> tuple:
        return self.spec.shape

    @property
    def ndim(self) -> int:
        return self.spec.ndim

    @property
    def batch_shape(self) -> tuple:
        return self.spec.batch_shape

    @property
    def placement(self) -> str:
        return self.spec.placement

    @property
    def levels(self) -> int:
        return self.leaf.levels

    @functools.cached_property
    def tag(self) -> str:
        """``<kind>_<shape>_b<rows>_<impl>``, with ``_<placement>`` when
        not local and ``_<verify>`` when verified: the name of the plan's
        jitted programs (``jit_counted_<tag>``, ``..._donated``,
        ``..._inverse``) and of its ``fft.plan.launch`` spans."""
        s = self.spec
        tag = (f"{s.kind}_{'x'.join(map(str, s.shape))}_b{s.rows}"
               f"_{s.impl}")
        if s.placement != "local":
            tag += f"_{s.placement}"
        if s.verify != "off":
            tag += f"_{s.verify}"
        return tag

    @property
    def fused_untangle(self) -> bool:
        """True when the r2c untangle epilogue fuses into one leaf kernel.

        False in the known n > 2*MAX_LEAF regime where the half-length
        transform is level-1 and the untangle runs as a host epilogue
        (byte-neutral there, still flop-halved — DESIGN.md §4), for all
        c2c plans, and for N-D plans (the N-D untangle is deferred past
        the leading-axis passes and runs vectorized on the host).
        """
        return (self._fast_r2c and self.spec.ndim == 1
                and self.leaf.levels == 1)

    # ------------------------------------------------------------------
    # analytic cost model (roofline numerators; DESIGN.md §3-4, §9)

    @property
    def flops_per_row(self) -> float:
        """Algorithmic complex-FLOPs per batch row (5 n log2 n convention).

        N-D is a sum over axis passes; the r2c fast path halves the
        working width after the contiguous-axis pass and adds the O(N/2)
        untangle (~10 real ops per bin).
        """
        s = self.spec
        n = s.n
        if n <= 1:
            return 0.0
        if not (self._fast_r2c or self._fast_r2c_pencil):
            return 5.0 * n * math.log2(n)
        m = s.shape[-1] // 2
        if s.ndim == 1:
            return 5.0 * m * math.log2(m) + 10.0 * m if m > 1 else 10.0 * m
        half_n = n // 2
        f = 10.0 * half_n  # untangle
        if m > 1:
            f += (half_n // m) * 5.0 * m * math.log2(m)
        for ax_len in s.shape[:-1]:
            f += (half_n // ax_len) * 5.0 * ax_len * math.log2(ax_len)
        return f

    @property
    def flops(self) -> float:
        return self.spec.rows * self.flops_per_row

    @property
    def gemm_macs_per_row(self) -> float:
        """Real MACs the matmul formulation issues per batch row."""
        s = self.spec
        if s.ndim > 1:
            # per-axis passes; identical for local / segmented / pencil
            # placements (the pencil runs exactly the local GEMMs)
            fast = self._fast_r2c or self._fast_r2c_pencil
            width = s.n // 2 if fast else s.n
            last = s.shape[-1] // 2 if fast else s.shape[-1]
            macs = ((width // max(last, 1))
                    * kplan.make_plan(max(last, 1)).gemm_macs)
            for ax_len in s.shape[:-1]:
                macs += (width // ax_len) * kplan.make_plan(ax_len).gemm_macs
            return macs
        if s.placement == "distributed":
            d = self.dist
            # pass 1: n2 length-n1 transforms; pass 2: n1 length-n2
            return (d.n2 * kplan.make_plan(d.n1).gemm_macs
                    + d.n1 * kplan.make_plan(d.n2).gemm_macs)
        return self.leaf.gemm_macs

    @property
    def gemm_macs(self) -> float:
        return self.spec.rows * self.gemm_macs_per_row

    @property
    def hbm_bytes_per_row(self) -> int:
        """Planar-f32 payload HBM bytes per batch row (table traffic excl.)."""
        s = self.spec
        if s.placement == "distributed":
            plane = _F32 * s.n
            per_pass = 2 * 2 * plane
            if s.ndim > 1:
                # pencil: ndim local passes + each of the ndim-1 exchange
                # legs' buffers landing in HBM (one round-trip per leg)
                legs = s.ndim - 1
                m1 = s.shape[-1] // 2 + 1
                if self._fast_r2c_pencil:
                    # every pass and leg moves the packed HALF volume; the
                    # global untangle re-reads the half planes and writes
                    # the m+1-bin one-sided spectrum (DESIGN.md §14)
                    half_pass = per_pass // 2
                    return ((s.ndim + legs) * half_pass
                            + 2 * _F32 * (s.n // 2)
                            + 2 * _F32 * (s.n // s.shape[-1]) * m1)
                bytes_ = s.ndim * per_pass + legs * per_pass
                if s.kind == "r2c":
                    # legacy c2c + one-sided slice fallback
                    bytes_ += 2 * _F32 * (s.n // s.shape[-1]) * m1
                return bytes_
            # 1-D: two local passes, each read 2 planes + write 2 planes,
            # plus the a2a buffers landing in HBM (one round-trip per a2a)
            # and, unfused, the elementwise twiddle's extra round-trip
            n_a2a = 3 if s.natural_order else 2
            bytes_ = 2 * per_pass + n_a2a * per_pass
            if not s.fuse_twiddle:
                bytes_ += per_pass
            return bytes_
        if s.ndim > 1:
            if s.kind == "r2c" and self._fast_r2c:
                return kplan.rfftn_hbm_bytes(s.shape)
            if s.kind == "r2c":
                m1 = s.shape[-1] // 2 + 1
                return (kplan.fftn_hbm_bytes(s.shape, s.layout)
                        + 2 * _F32 * (s.n // s.shape[-1]) * m1)
            return kplan.fftn_hbm_bytes(s.shape, s.layout)
        if s.kind == "r2c" and self._fast_r2c:
            return kplan.rfft_hbm_bytes(s.n)
        if s.kind == "r2c":
            # legacy full transform + sliced one-sided write
            return (kplan.fft_hbm_bytes(s.n, s.layout)
                    + 2 * _F32 * (s.n // 2 + 1))
        return kplan.fft_hbm_bytes(s.n, s.layout)

    @property
    def hbm_bytes(self) -> int:
        return self.spec.rows * self.hbm_bytes_per_row

    @property
    def collective_bytes(self) -> int:
        """Total planar payload crossing ICI (distributed placement only).

        Mirrors `DistPlan.collective_bytes_per_device`, which now folds the
        exchange count — transposed-out plans (natural_order=False) skip
        exchange #3 and report one leg fewer.
        """
        if self.dist is None:
            return 0
        return self.dist.d * self.dist.collective_bytes_per_device

    @property
    def exposed_collective_bytes(self) -> int:
        """Collective bytes the overlap pipeline cannot hide (fill/drain
        slab per exchange — `DistPlan.exposed_collective_bytes_per_device`).
        Equal to `collective_bytes` for overlap="off" plans."""
        if self.dist is None:
            return 0
        return self.dist.d * self.dist.exposed_collective_bytes_per_device

    @property
    def hidden_collective_bytes(self) -> int:
        """Collective bytes the chunked ppermute pipeline overlaps with
        local MXU compute (the predicted overlap win's numerator)."""
        return self.collective_bytes - self.exposed_collective_bytes

    @property
    def per_leg_collective_bytes(self) -> tuple:
        """Total payload crossing ICI per exchange leg, in leg order
        (pencil: axis nd-2 first; 1-D: the three four-step exchanges).
        Sums to `collective_bytes`; () for non-distributed plans. The
        tuner ranks candidates against this per-leg accounting."""
        if self.dist is None:
            return ()
        return tuple(self.dist.d * b
                     for b in self.dist.per_leg_bytes_per_device)

    @property
    def per_leg_exposed_collective_bytes(self) -> tuple:
        """Per-leg structurally exposed (fill/drain) payload; sums to
        `exposed_collective_bytes` up to integer division."""
        if self.dist is None:
            return ()
        return tuple(self.dist.d * b
                     for b in self.dist.per_leg_exposed_bytes_per_device)

    @property
    def verify_flops(self) -> float:
        """Flops the spec's ABFT mode adds (O(rows*n) invariant checks;
        zero for verify="off"). Kept separate from `flops` — that is the
        transform's algorithmic count, which verification never changes."""
        from repro.core.resilience.verify import verify_flops
        s = self.spec
        return float(verify_flops(s.verify, s.n, max(s.rows, 1)))

    @property
    def verify_hbm_bytes(self) -> int:
        """Extra host/HBM traffic of the spec's ABFT mode (re-reads of the
        input/output planes for the energy and checksum reductions)."""
        from repro.core.resilience.verify import verify_hbm_bytes
        s = self.spec
        return verify_hbm_bytes(s.verify, s.n, max(s.rows, 1))

    @property
    def verify_overhead(self) -> float:
        """Analytic verification overhead: verify_flops / flops (0.0 when
        either side is zero) — the cost-model number the bench_verify gate
        reports alongside the measured wall-clock ratio."""
        f = self.flops
        return self.verify_flops / f if f else 0.0

    # ------------------------------------------------------------------
    # executables

    @property
    def trace_counts(self) -> dict:
        return dict(self._traces)

    @property
    def trace_count(self) -> int:
        return sum(self._traces.values())

    @property
    def executable(self):
        """The id-stable jit'd forward callable (compiled once per shape)."""
        return self._forward()[1]

    def _forward(self):
        if self._fwd is None:
            with self._build_lock:
                if self._fwd is None:
                    self._fwd = self._build_forward()
        return self._fwd

    def _build_forward(self):
        s = self.spec
        in_shardings = out_shardings = None
        if s.placement == "local":
            if s.kind == "c2c" and s.ndim == 1:
                def inner(xr, xi):
                    return executors.fft(
                        xr, xi, impl=s.impl, interpret=s.interpret,
                        batch_tile=s.batch_tile, layout=s.layout)
            elif s.kind == "c2c":
                def inner(xr, xi):
                    return executors.fftn(
                        xr, xi, s.shape, impl=s.impl, interpret=s.interpret,
                        batch_tile=s.batch_tile, layout=s.layout)
            elif s.ndim == 1:
                def inner(x):
                    return executors.rfft(
                        x, impl=s.impl, interpret=s.interpret,
                        batch_tile=s.batch_tile, layout=s.layout)
            else:
                def inner(x):
                    return executors.rfftn(
                        x, s.shape, impl=s.impl, interpret=s.interpret,
                        batch_tile=s.batch_tile, layout=s.layout)
        elif s.placement == "segmented":
            from repro.core.fft import segmented
            inner, in_shardings, out_shardings = segmented.build_segmented(
                self.mesh, s.axes, kind=s.kind, shape=s.shape, impl=s.impl,
                interpret=s.interpret, layout=s.layout)
        elif s.ndim == 1:
            from repro.core.fft import distributed
            inner = distributed.build_distributed(
                s.n, self.mesh, s.axes, impl=s.impl,
                natural_order=s.natural_order, fuse_twiddle=s.fuse_twiddle,
                interpret=s.interpret, layout=s.layout,
                overlap=None if s.overlap == "off" else s.overlap)
        else:
            from repro.core.fft import distributed
            build_kw = dict(
                impl=s.impl, interpret=s.interpret, layout=s.layout,
                batch_tile=s.batch_tile,
                overlap=None if s.overlap == "off" else s.overlap)
            if s.kind == "c2c":
                inner = distributed.build_pencil(s.shape, self.mesh,
                                                 s.axes, **build_kw)
            elif self._fast_r2c_pencil:
                half_pencil = distributed.build_pencil_r2c(
                    s.shape, self.mesh, s.axes, **build_kw)
                vr, vi = (jnp.asarray(a)
                          for a in kplan.rfft_twiddle(s.shape[-1]))
                nd = s.ndim

                def inner(x):
                    # flop-halved r2c pencil: the packed half-width volume
                    # runs the contiguous pass + every exchange leg, and
                    # the ONE N-D untangle runs on the GLOBAL half
                    # spectrum outside the shard_map — exactly where the
                    # local rfftn applies it, so this is bitwise vs the
                    # local oracle (DESIGN.md §14)
                    zr, zi = half_pencil(x)
                    return executors._untangle_nd(zr, zi, vr, vi, nd)
            else:
                pencil = distributed.build_pencil(s.shape, self.mesh,
                                                  s.axes, **build_kw)
                m1 = s.shape[-1] // 2 + 1

                def inner(x):
                    # fallback r2c pencil (grid cannot split the half
                    # width, or non-GEMM impl): ride the c2c engine and
                    # slice the one-sided spectrum (global slice, outside
                    # the shard_map — same exchange-leg count, not
                    # flop-halved)
                    yr, yi = pencil(x, jnp.zeros_like(x))
                    return yr[..., :m1], yi[..., :m1]

        def counted(*args):
            # python side effect: runs once per trace OF THIS PLAN'S JIT,
            # so this counts retraces — the "zero retrace" observable. The
            # tracer path below inlines `inner` instead, so outer-jit
            # traces by callers never pollute the count.
            self._traces["forward"] += 1
            return inner(*args)

        self._name(counted)
        self._fwd_shardings = (in_shardings, out_shardings)
        if in_shardings is not None:
            jitted = jax.jit(counted, in_shardings=in_shardings,
                             out_shardings=out_shardings)
        else:
            jitted = jax.jit(counted)
        return inner, jitted

    def _forward_donated(self):
        """The forward jit with every operand buffer donated.

        A distinct executable from `_forward()` (donation is a compile-time
        property), so its first call costs one extra trace of this plan;
        after that, repeat calls are zero-retrace like the plain path. On
        backends without donation support (CPU) XLA ignores the donation
        and the call stays correct.
        """
        if self._fwd_donated is None:
            with self._build_lock:
                if self._fwd_donated is None:
                    inner = self._forward()[0]
                    nargs = 1 if self.spec.kind == "r2c" else 2

                    def counted(*args):
                        self._traces["forward"] += 1
                        return inner(*args)

                    self._name(counted, "_donated")
                    in_sh, out_sh = self._fwd_shardings
                    donate = tuple(range(nargs))
                    if in_sh is not None:
                        self._fwd_donated = jax.jit(
                            counted, in_shardings=in_sh, out_shardings=out_sh,
                            donate_argnums=donate)
                    else:
                        self._fwd_donated = jax.jit(counted,
                                                    donate_argnums=donate)
        return self._fwd_donated

    def _inverse(self):
        if self._inv is None:
            with self._build_lock:
                if self._inv is None:
                    self._inv = self._build_inverse()
        return self._inv

    def _build_inverse(self):
        s = self.spec
        fwd_inner = self._forward()[0]
        if s.kind == "c2c":
            if (s.placement == "distributed" and s.ndim == 1
                    and not s.natural_order):
                raise NotImplementedError(
                    "execute_inverse needs natural_order=True: the "
                    "transposed-out forward returns o1-major block order, "
                    "so the conjugation identity would invert a permuted "
                    "spectrum. Plan the inverse leg with "
                    "natural_order=True (TRANSPOSED_OUT consumers apply "
                    "their pointwise op, then run a separate inverse plan)")
            n = s.n  # total points: the N-D conjugation identity's scale

            def inner(yr, yi):
                # conjugation identity; the forward must return natural
                # order for this to be the true inverse (checked above —
                # the 2-D pencil is always natural-order, just re-sharded)
                ar, ai = fwd_inner(yr, -yi)
                return ar / n, -ai / n
        else:
            if s.placement != "local":
                raise NotImplementedError(
                    f"execute_inverse for r2c plans is local-only, "
                    f"got placement={s.placement!r}")
            if s.ndim == 1:
                def inner(yr, yi):
                    return executors.irfft(
                        yr, yi, impl=s.impl, interpret=s.interpret,
                        batch_tile=s.batch_tile, layout=s.layout)
            else:
                def inner(yr, yi):
                    return executors.irfftn(
                        yr, yi, s.shape, impl=s.impl, interpret=s.interpret,
                        batch_tile=s.batch_tile, layout=s.layout)

        def counted(yr, yi):
            self._traces["inverse"] += 1
            return inner(yr, yi)

        self._name(counted, "_inverse")
        return inner, jax.jit(counted)

    def _name(self, counted, variant: str = "") -> None:
        """Name a jitted function so that its XLA module reads
        ``jit_counted_<tag><variant>`` in a device trace."""
        counted.__name__ = counted.__qualname__ = (
            f"counted_{self.tag}{variant}")

    def _launch_span(self, variant: str, *operands):
        """The ``fft.plan.launch`` span of a host call: shape checks, jit
        cache lookup, any H2D copy of host operands and the enqueue. None
        inside a caller's trace."""
        if _is_tracer(*operands):
            return contextlib.nullcontext()
        return spans.span("fft.plan.launch", plan=self.tag + variant)

    # ------------------------------------------------------------------

    def _check_shape(self, got, expected, what):
        if tuple(got) != expected:
            raise ValueError(
                f"{what}: plan was built for shape {expected} "
                f"(batch_shape={self.spec.batch_shape}, "
                f"shape={self.spec.shape}), got {tuple(got)}")

    def execute(self, xr, xi):
        """Forward c2c transform of planar (*batch_shape, *shape) float32
        arrays."""
        if self.spec.kind != "c2c":
            raise ValueError(
                "execute() is for kind='c2c' plans; use execute_real(x) "
                "on this r2c plan")
        with self._launch_span("", xr, xi):
            shape = self.spec.operand_shape
            self._check_shape(xr.shape, shape, "execute")
            self._check_shape(xi.shape, shape, "execute")
            raw, jitted = self._forward()
            if _is_tracer(xr, xi):
                return raw(xr, xi)
            return jitted(xr, xi)

    def execute_real(self, x):
        """Forward r2c transform: real (*batch_shape, *shape) -> planar
        one-sided (*batch_shape, *shape[:-1], shape[-1]//2 + 1) spectrum."""
        if self.spec.kind != "r2c":
            raise ValueError(
                "execute_real() is for kind='r2c' plans; use "
                "execute(xr, xi) on this c2c plan")
        with self._launch_span("", x):
            self._check_shape(x.shape, self.spec.operand_shape,
                              "execute_real")
            raw, jitted = self._forward()
            if _is_tracer(x):
                return raw(x)
            return jitted(x)

    def execute_async(self, *operands, donate: bool = False):
        """Launch the forward transform WITHOUT synchronizing.

        Returns unrealized device arrays immediately (JAX async dispatch);
        the caller decides where the sync point is — e.g. the stream
        executor's in-flight window boundary (`core/pipeline/stream.py`)
        realizes results in its writeback stage while later batches are
        already dispatched. `execute`/`execute_real` have the same launch
        semantics but are documented as the simple path; this entry exists
        so pipelined callers state their intent and get `donate`.

        Operands: `(xr, xi)` for c2c plans, `(x,)` for r2c.
        donate=True compiles a variant that donates the operand buffers to
        XLA, letting outputs alias the staging buffers' device memory (the
        operands must not be reused after the call). Ignored (correctly,
        with no aliasing) on backends without donation support.
        """
        nargs = 1 if self.spec.kind == "r2c" else 2
        if len(operands) != nargs:
            raise ValueError(
                f"execute_async on a {self.spec.kind!r} plan takes "
                f"{nargs} operand(s), got {len(operands)}")
        with self._launch_span("_donated" if donate else "", *operands):
            shape = self.spec.operand_shape
            for op in operands:
                self._check_shape(op.shape, shape, "execute_async")
            if _is_tracer(*operands):
                return self._forward()[0](*operands)
            if donate:
                # backends without donation support ignore the hint
                # (correct, no aliasing); any "donated buffers were not
                # usable" warning is deduped per call site by the default
                # warnings filter
                return self._forward_donated()(*operands)
            return self._forward()[1](*operands)

    def execute_inverse(self, yr, yi):
        """Inverse transform.

        c2c: planar spectrum -> planar signal (both (*batch_shape, *shape)).
        r2c: one-sided (*batch_shape, *shape[:-1], shape[-1]//2 + 1)
        spectrum -> real (*batch_shape, *shape) signal.
        """
        s = self.spec
        if s.kind == "c2c":
            shape = s.operand_shape
        else:
            shape = (*s.batch_shape, *s.shape[:-1], s.shape[-1] // 2 + 1)
        with self._launch_span("_inverse", yr, yi):
            self._check_shape(yr.shape, shape, "execute_inverse")
            self._check_shape(yi.shape, shape, "execute_inverse")
            raw, jitted = self._inverse()
            if _is_tracer(yr, yi):
                return raw(yr, yi)
            return jitted(yr, yi)


# ---------------------------------------------------------------------------
# the facade


def plan(kind: str = "c2c", *, n: int | None = None, shape=None,
         batch_shape=(), mesh=None,
         placement: str = "auto", layout: str = "zero_copy",
         impl: str = "matfft", precision: str = "f32",
         interpret: bool | None = None, batch_tile: int | None = None,
         axes=None, natural_order: bool = True,
         fuse_twiddle: bool = False, overlap="auto",
         r2c_axis: int = -1, fallback: str = "error",
         verify: str = "off", tune: bool = False, wisdom_path=None,
         tune_config=None,
         store=None, work_dir=None, budget_bytes: int | None = None,
         job_config=None):
    """Resolve a transform spec and return the cached `ExecutablePlan`.

    Args:
      kind: "c2c" (planar complex) or "r2c" (real input, one-sided output).
      n: 1-D transform length — sugar for ``shape=(n,)``; pass exactly one
        of ``n``/``shape`` (power-of-two axes; real length for r2c).
      shape: N-D transform shape over the TRAILING operand axes, e.g.
        ``shape=(n0, n1)`` for a 2-D image FFT. The contiguous (last) axis
        runs the level-0/1 four-step (up to MAX_LEAF**2); earlier axes run
        as single column-kernel passes (up to MAX_LEAF each). Scalar-n and
        the equivalent 1-tuple resolve to the SAME cache key.
      batch_shape: leading batch dims of the operands; () for a single
        signal/image (required for placement="distributed").
      mesh: jax Mesh for segmented/distributed placements.
      placement: "auto" (heuristic over shape/batch/mesh), "local",
        "segmented" (map-only batch sharding, zero collectives), or
        "distributed" (1-D: cross-device four-step, 3 exchanges; 2-D:
        pencil decomposition, ONE exchange — DESIGN.md §9).
      layout: "zero_copy" (default) or "copy" (measured legacy baseline;
        for N-D the naive transpose-per-axis path bench_fft2.py gates on).
      impl: leaf kernel ("matfft" MXU GEMM, "stockham" VPU, "ref" jnp).
      precision: "f32" (reserved for future variants).
      interpret: Pallas interpret-mode override; None = auto off-TPU.
      batch_tile: kernel batch/column tile override.
      axes: mesh axes to use; None = every axis of the mesh.
      natural_order / fuse_twiddle: 1-D distributed-placement options
        (DESIGN.md §2; ignored elsewhere — the pencil is always natural).
      overlap: distributed-placement exchange engine (DESIGN.md §8):
        "off" = monolithic all_to_alls; an int = that many ppermute
        pipeline slabs per exchange, hidden behind the local FFTs (must
        divide the per-device slab widths — validated at plan time);
        "auto" picks a chunk count or "off" from the size and ring.
        Resolved before the cache key, so overlap="auto" and the
        equivalent explicit value share one plan.
      r2c_axis: which transform axis carries the real-to-complex halving;
        only the contiguous axis (-1) is supported — anything else is a
        plan-time ValueError (the packed-real reshape is only free there).
      fallback: "error" (default) raises when the requested strategy can't
        be built; "degrade" re-plans instead of raising when the mesh has
        lost devices (core/resilience/meshstate.py) or the mesh-bound
        strategy is unsatisfiable — first on the largest healthy pow2
        sub-mesh, then mesh-free/local. Every downgrade drops the stale
        mesh's cached plans (`invalidate_mesh`) and records a
        "plan_downgrade" resilience event (DESIGN.md §10).
      verify: ABFT mode for consumers that run the plan's invariant
        checks (DESIGN.md §13): "off" (default), "parseval" (per-member
        energy invariant), or "abft" (linearity checksum row per batch).
        Resolved pre-cache-key, so verified and unverified plans are
        distinct cache entries; `verify_flops`/`verify_hbm_bytes`/
        `verify_overhead` report the mode's analytic cost.
      tune: measure instead of model (DESIGN.md §14): the autotuner in
        `repro.fft.tuner` times the real candidate space — overlap chunk
        count + exchange engine, layout, batch tile (and OOC panel
        heights) — on small representative shards, applies the winner's
        knobs, and persists the decision as wisdom keyed on resolved
        spec + mesh fingerprint + backend. A wisdom hit is a pure lookup:
        zero measurement, zero retrace (counted by cache_info()'s
        `wisdom_hits`). The tuned knobs resolve BEFORE the cache key, so
        tuned and hand-specified-equivalent plans share one cache entry.
      wisdom_path: wisdom file override (default
        ~/.cache/repro_fft/wisdom.json); tune=True only.
      tune_config: `tuner.TuneConfig` override (seed, repeats, injectable
        timer/measurer, model constants); tune=True only.

    Same resolved spec (and mesh) -> the SAME plan object, with its jit'd
    executables and twiddle tables already built.
    """
    if fallback not in ("error", "degrade"):
        raise ValueError(
            f"fallback must be 'error' or 'degrade', got {fallback!r}")

    if placement == "out_of_core":
        # the operand lives in a BlockStore and the plan carries live
        # store/manifest state, so it is built here directly (never
        # process-cached) — the per-pass FFTs it launches are the cached
        # ExecutablePlans, which is where the reuse actually matters
        if kind != "c2c":
            raise ValueError(
                "placement='out_of_core' streams the four-step c2c "
                "decomposition; run real captures as packed c2c")
        if shape is not None:
            shape_t = (shape,) if isinstance(shape, int) else tuple(shape)
            if n is not None or len(shape_t) != 1:
                raise ValueError(
                    f"placement='out_of_core' transforms ONE 1-D signal; "
                    f"pass n= (or a 1-tuple shape), got shape={shape}")
            n = int(shape_t[0])
        if n is None:
            raise ValueError("placement='out_of_core' requires n=")
        if batch_shape not in ((), None):
            raise ValueError(
                f"placement='out_of_core' takes no batch_shape, got "
                f"{batch_shape}; the panel batching is internal")
        if mesh is not None:
            raise ValueError(
                "placement='out_of_core' streams through storage on one "
                "host; it takes no mesh=")
        if impl not in spec_mod.IMPLS:
            raise ValueError(
                f"unknown fft impl {impl!r}; expected one of "
                f"{spec_mod.IMPLS}")
        if store is None or work_dir is None or budget_bytes is None:
            raise ValueError(
                "placement='out_of_core' requires store= (the BlockStore "
                "holding the operand), work_dir= (tiles/manifests/output), "
                "and budget_bytes= (the host working-set cap)")
        from repro.core.fft.outofcore import plan_out_of_core
        panel_scale = 1
        if tune:
            from repro.fft import tuner
            panel_scale, rep = tuner.tune_out_of_core(
                int(n), int(budget_bytes), impl=impl,
                block_bytes=getattr(store, "block_bytes", None),
                wisdom_path=wisdom_path, config=tune_config)
            if rep.wisdom_hit:
                with _CACHE_LOCK:
                    _CACHE_INFO["wisdom_hits"] += 1
        return plan_out_of_core(int(n), store, work_dir, int(budget_bytes),
                                impl=impl, config=job_config, verify=verify,
                                panel_scale=panel_scale)
    if store is not None or work_dir is not None or budget_bytes is not None:
        raise ValueError(
            "store=/work_dir=/budget_bytes= apply only to "
            "placement='out_of_core'")

    # resolve interpret-mode auto-detection BEFORE the spec is built, so
    # interpret=None and the equivalent explicit bool key the same plan
    interpret = resolve_interpret(interpret)
    if impl == "stockham" and jax.default_backend() == "tpu":
        raise ValueError(
            "impl='stockham' is an interpret-mode baseline whose kernel does "
            "not lower on a TPU; use impl='matfft' (or 'ref')")

    def _degrade(reason: str):
        """Graceful-degradation chain: shrunk healthy mesh, then local.

        Returns the downgraded plan, or None when every candidate fails
        (the caller re-raises its own error). The stale mesh's cached
        plans are dropped first — they capture collectives over devices
        that no longer answer, so a later cache hit on the old key would
        resurrect a hung strategy after the mesh heals its entry.
        """
        from repro.core.resilience import meshstate
        from repro.core.resilience.events import record_event
        dropped = invalidate_mesh(mesh)
        sub = meshstate.shrunk_mesh(mesh)
        candidates = []
        if sub is not None:
            candidates.append((sub, placement))
            if placement not in ("auto", "local"):
                candidates.append((sub, "auto"))
        candidates.append((None, "local"))
        for sub_mesh, sub_placement in candidates:
            try:
                p = plan(kind=kind, n=n, shape=shape,
                         batch_shape=batch_shape, mesh=sub_mesh,
                         placement=sub_placement, layout=layout, impl=impl,
                         precision=precision, interpret=interpret,
                         batch_tile=batch_tile, axes=None,
                         natural_order=natural_order,
                         fuse_twiddle=fuse_twiddle, overlap=overlap,
                         r2c_axis=r2c_axis, fallback="error",
                         verify=verify)
            except (ValueError, NotImplementedError):
                continue
            record_event(
                "plan_downgrade", reason=reason,
                requested_placement=placement,
                resolved_placement=p.placement,
                from_devices=int(mesh.devices.size),
                to_devices=(int(sub_mesh.devices.size)
                            if sub_mesh is not None else 0),
                epoch=meshstate.epoch(), plans_invalidated=dropped)
            return p
        return None

    if fallback == "degrade" and mesh is not None:
        from repro.core.resilience import meshstate
        if not meshstate.mesh_healthy(mesh):
            p = _degrade("mesh_degraded")
            if p is not None:
                return p
            raise RuntimeError(
                f"fallback='degrade': no viable plan for a mesh with "
                f"{len(meshstate.healthy_devices(mesh))}/"
                f"{mesh.devices.size} healthy devices")

    num_devices = None
    if mesh is not None:
        if axes is None:
            axes = tuple(mesh.shape.keys())
        else:
            if isinstance(axes, str):
                axes = (axes,)
            axes = tuple(a for a in axes if a in mesh.shape)
        if not axes:
            raise ValueError(
                f"none of the requested axes exist in mesh axes "
                f"{tuple(mesh.shape.keys())}")
        num_devices = math.prod(mesh.shape[a] for a in axes)
    elif axes is not None:
        raise ValueError("axes= requires mesh=")
    axis_sizes = (tuple(mesh.shape[a] for a in axes)
                  if mesh is not None else None)

    if tune:
        # measure-then-plan: the tuner picks layout/batch_tile/overlap and
        # the winning knobs resolve into the spec BEFORE the cache key —
        # a later plan() with the same knobs spelled out is the same plan.
        # A wisdom hit performs zero measurements and zero retraces.
        from repro.fft import tuner
        knobs, report = tuner.tune(
            kind=kind, n=n, shape=shape, batch_shape=batch_shape,
            mesh=mesh, axes=axes, num_devices=num_devices,
            axis_sizes=axis_sizes, placement=placement, layout=layout,
            impl=impl, precision=precision, interpret=interpret,
            batch_tile=batch_tile, natural_order=natural_order,
            fuse_twiddle=fuse_twiddle, overlap=overlap, r2c_axis=r2c_axis,
            verify=verify, wisdom_path=wisdom_path, config=tune_config)
        layout = knobs.get("layout", layout)
        batch_tile = knobs.get("batch_tile", batch_tile)
        overlap = knobs.get("overlap", overlap)
        if report.wisdom_hit:
            with _CACHE_LOCK:
                _CACHE_INFO["wisdom_hits"] += 1

    try:
        resolved = spec_mod.resolve(
            kind=kind, n=n, shape=shape, batch_shape=batch_shape,
            placement=placement, layout=layout, impl=impl,
            precision=precision, interpret=interpret, batch_tile=batch_tile,
            num_devices=num_devices, axes=axes, natural_order=natural_order,
            fuse_twiddle=fuse_twiddle, overlap=overlap, r2c_axis=r2c_axis,
            verify=verify, axis_sizes=axis_sizes)
    except ValueError:
        # mesh-bound strategy unsatisfiable (e.g. too few devices for the
        # split): degrade walks the same chain instead of raising. A
        # mesh-free failure is a genuine spec error — nothing to degrade
        # to — so it always propagates.
        if fallback == "degrade" and mesh is not None:
            p = _degrade("resolve_failed")
            if p is not None:
                return p
        raise

    # local plans don't touch the mesh -> key them mesh-free so the same
    # spec planned with and without a mesh unifies
    mesh_for_key = None if resolved.placement == "local" else mesh
    key = (resolved, mesh_for_key)
    with _CACHE_LOCK:
        cached = _PLAN_CACHE.get(key)
        if cached is not None:
            _CACHE_INFO["hits"] += 1
            return cached
        _CACHE_INFO["misses"] += 1
        p = ExecutablePlan(resolved, mesh_for_key)
        _PLAN_CACHE[key] = p
        return p


# ---------------------------------------------------------------------------
# 2-D convenience wrappers (numpy.fft.fft2/rfft2 conventions): plan over the
# trailing two axes, execute through the cached plan


def _check_2d(a, what: str) -> None:
    # numpy.fft.fft2/rfft2 raise for <2-D input; silently planning a 1-D
    # transform here would hand back a wrong-dimensionality spectrum
    if a.ndim < 2:
        raise ValueError(
            f"{what} transforms the trailing TWO axes; got a "
            f"{a.ndim}-D operand of shape {tuple(a.shape)} — use the 1-D "
            f"plan (n=...) for single-axis transforms")


def fft2(xr, xi, **kw):
    """Forward 2-D FFT over the trailing two axes of planar float32 arrays.

    ``kw`` passes through to `plan` (mesh=, placement=, overlap=, ...);
    repeat calls with the same shapes hit the plan cache.
    """
    _check_2d(xr, "fft2")
    p = plan(kind="c2c", shape=tuple(xr.shape[-2:]),
             batch_shape=tuple(xr.shape[:-2]), **kw)
    return p.execute(xr, xi)


def ifft2(yr, yi, **kw):
    """Inverse 2-D FFT over the trailing two axes (planar)."""
    _check_2d(yr, "ifft2")
    p = plan(kind="c2c", shape=tuple(yr.shape[-2:]),
             batch_shape=tuple(yr.shape[:-2]), **kw)
    return p.execute_inverse(yr, yi)


def rfft2(x, **kw):
    """Real-input 2-D FFT: (*batch, n0, n1) real -> planar one-sided
    (*batch, n0, n1//2 + 1) spectrum (numpy.fft.rfft2 convention)."""
    _check_2d(x, "rfft2")
    p = plan(kind="r2c", shape=tuple(x.shape[-2:]),
             batch_shape=tuple(x.shape[:-2]), **kw)
    return p.execute_real(x)


def irfft2(yr, yi, shape=None, **kw):
    """Inverse of rfft2: one-sided spectrum -> real (*batch, n0, n1).

    ``shape`` is the real-image shape (n0, n1); default reconstructs the
    even length 2*(yr.shape[-1] - 1) like numpy.fft.irfft2.
    """
    _check_2d(yr, "irfft2")
    if shape is None:
        shape = (yr.shape[-2], 2 * (yr.shape[-1] - 1))
    p = plan(kind="r2c", shape=tuple(shape),
             batch_shape=tuple(yr.shape[:-2]), **kw)
    return p.execute_inverse(yr, yi)


def cache_info() -> dict:
    """Process-level plan-cache stats:
    {entries, hits, misses, invalidations, wisdom_hits, size}.

    ``entries`` is the live plan count (``size`` kept as its legacy
    alias); ``invalidations`` counts plans dropped by `invalidate_mesh` /
    `clear_plan_cache` over the process lifetime. ``wisdom_hits`` counts
    tune=True plans whose knobs came from the wisdom file with zero
    measurement — distinct from ``hits``: a wisdom hit that still builds
    a new ExecutablePlan is a plan-cache MISS (it re-traces), and only
    lookups returning an existing plan object count as hits. Workloads
    that churn the cache across phases (the out-of-core job's two pass
    lengths, the degrade path's mesh drops) report this dict —
    launch/fft_job.py carries it in every run report.
    """
    with _CACHE_LOCK:
        return {**_CACHE_INFO, "entries": len(_PLAN_CACHE),
                "size": len(_PLAN_CACHE)}


def invalidate_mesh(mesh) -> int:
    """Drop every cached plan keyed on ``mesh``; returns how many.

    Called by the degrade path when the mesh loses devices: the cached
    plans' collectives span the dead devices, so serving them from the
    cache would hand back a strategy that can never complete. Local plans
    (keyed mesh-free) are untouched.
    """
    if mesh is None:
        return 0
    with _CACHE_LOCK:
        stale = [k for k in _PLAN_CACHE
                 if k[1] is not None and k[1] == mesh]
        for k in stale:
            del _PLAN_CACHE[k]
        _CACHE_INFO["invalidations"] += len(stale)
    return len(stale)


def clear_plan_cache() -> None:
    """Drop every cached plan (tests/benchmarks; compiled fns are freed)
    and reset the cache counters."""
    with _CACHE_LOCK:
        _PLAN_CACHE.clear()
        _CACHE_INFO["hits"] = 0
        _CACHE_INFO["misses"] = 0
        _CACHE_INFO["invalidations"] = 0
        _CACHE_INFO["wisdom_hits"] = 0
