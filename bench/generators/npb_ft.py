"""NPB FT iterations on a device mesh.

Set-up makes the complex field x on the mesh from the seed (planar
float32, the configuration's input sharding), runs the program's forward
3-D transform on it once (``plan(shape=..., placement="distributed")``),
keeps the spectrum u0 in the input sharding and drops x. One jitted step
then does what an NPB FT iteration does:

    v   = conj(u0 * exp(-4 pi^2 alpha t |k|^2))    evolve, from indices
    y   = plan.execute(v)                          the program's pencil
    u_t = conj(y)                                  NPB's unnormalized inverse
    the field at NPB's checksum points             gathered where they live

and returns only those points, so nothing of the size of the grid leaves
the step. The window runs the step for t = 1, 2, .., niter, 1, .. one at
a time (the step's buffers fill most of a chip) until ``seconds`` have
passed; the metric is the window over the iterations.

The step gathers NPB's checksum points and ``points_per_chip`` more
drawn from the seed inside each chip's block of the output. NPB's points
alone miss whole chips: at class D their x is j mod 2048 for j = 1..1024,
so all but one lie in the half of x that the model = 0 chips hold.

Checked: ``sample_points`` of NPB's points drawn from the seed and every
drawn point, at the iterations of t drawn from the seed (always the last
t, whose kernel is the widest), against the configuration's float64
reference computed from x, which is made again from the seed once the
window has closed.

Traffic parameters: ``sample_points`` (of the checksum points),
``points_per_chip`` and ``sampled_iterations`` (values of t checked,
every iteration of each).
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from bench import check, harness, work


def _field(cell: harness.Cell):
    """(mesh, make): ``make(key)`` is the jitted field x on the mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cfg = cell.config
    shape = tuple(cfg["shape"])
    mesh = Mesh(np.array(jax.devices()[:cell.chips]).reshape(
        cfg["mesh"]["shape"]), tuple(cfg["mesh"]["axes"]))
    s_in = NamedSharding(mesh, P(*cfg["in_spec"]))

    def make(key):
        kr, ki = jax.random.split(key)
        return (jax.random.normal(kr, shape, jnp.float32),
                jax.random.normal(ki, shape, jnp.float32))

    return mesh, jax.jit(make, out_shardings=(s_in, s_in))


def _samples(cell: harness.Cell, ran: list) -> list:
    """The values of t checked: the last t that ran, whose kernel is the
    widest, and others drawn from the seed."""
    rng = harness.numpy_rng(cell.seed, 3)
    k = min(int(cell.traffic["sampled_iterations"]), len(ran))
    others = rng.choice(ran[:-1], k - 1, replace=False) if k > 1 else []
    return sorted({ran[-1], *(int(t) for t in others)})


def _points(cell: harness.Cell):
    """(points the step gathers, indices of those checked): NPB's
    checksum points, then ``points_per_chip`` drawn from the seed inside
    each chip's block of the output (``out_spec`` over the mesh); checked
    are ``sample_points`` of NPB's drawn from the seed and every drawn
    one."""
    cfg, tr = cell.config, cell.traffic
    shape = tuple(cfg["shape"])
    npb = cell.reference.checksum_points(shape, cfg["checksum_points"])
    mesh = dict(zip(cfg["mesh"]["axes"], cfg["mesh"]["shape"]))
    rng = harness.numpy_rng(cell.seed, 4)
    k = int(tr["points_per_chip"])
    drawn = []
    for coords in itertools.product(*(range(n) for n in mesh.values())):
        at = dict(zip(mesh, coords))
        cols = []
        for n, m in zip(shape, cfg["out_spec"]):
            local = n // mesh[m] if m else n
            lo = at[m] * local if m else 0
            cols.append(rng.integers(lo, lo + local, k))
        drawn.append(np.stack(cols, axis=1))
    pts = np.concatenate([npb, *drawn]).astype(np.int32)
    sel = np.concatenate([
        np.sort(rng.choice(len(npb), int(tr["sample_points"]),
                           replace=False)),
        np.arange(len(npb), len(pts))])
    return pts, sel


def control(cell: harness.Cell) -> list:
    """The check with the reference in bf16 x3 products
    (``Precision.HIGH``) in the program's place, for the values of t a
    window that ran every t would check."""
    cfg = cell.config
    _, make = _field(cell)
    xr, xi = (np.asarray(a) for a in make(harness.jax_key(cell.seed)))
    checked = _samples(cell, list(range(1, int(cfg["niter"]) + 1)))
    pts, sel = _points(cell)
    want = cell.reference.points(xr, xi, checked, cfg["alpha"], pts[sel])
    got = cell.reference.control_points(xr, xi, checked, cfg["alpha"],
                                        pts[sel])
    err = max(check.rel_l2(g[None], w[None]) for g, w in zip(got, want))
    return [("max_rel_l2", err, cfg["check"]["max_rel_l2"])]


def run(cell: harness.Cell, counter: harness.CompileCounter,
        transform=None) -> harness.Outcome:
    """``transform`` replaces the program's forward pencil in the step
    (tests plant faults there)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import repro.fft as fft_api

    cfg, tr = cell.config, cell.traffic
    shape = tuple(cfg["shape"])
    niter, alpha = int(cfg["niter"]), float(cfg["alpha"])
    mesh, make = _field(cell)
    devices = list(mesh.devices.flat)
    in_spec, out_spec = P(*cfg["in_spec"]), P(*cfg["out_spec"])
    s_in = NamedSharding(mesh, in_spec)
    plan = fft_api.plan(kind="c2c", shape=shape, mesh=mesh,
                        placement="distributed", impl=cfg["impl"],
                        overlap=cfg["overlap"])
    forward = transform or plan.execute
    key = harness.jax_key(cell.seed)
    xr, xi = make(key)
    yr, yi = plan.execute(xr, xi)
    del xr, xi
    u0r, u0i = jax.jit(lambda a, b: (a, b),
                       out_shardings=(s_in, s_in))(yr, yi)
    del yr, yi

    pts, sel = _points(cell)
    pts_dev = jax.device_put(pts, NamedSharding(mesh, P()))
    k2 = [jnp.asarray(cell.reference.signed_index(n).astype(np.float32)
                      ** 2) for n in shape]
    local = [shape[a] // (mesh.shape[m] if m else 1)
             for a, m in enumerate(cfg["out_spec"])]

    def gather(yr, yi, p):
        # each device picks the points it holds; psum joins them
        idx, own = [], True
        for a, m in enumerate(cfg["out_spec"]):
            off = jax.lax.axis_index(m) * local[a] if m else 0
            i = p[:, a] - off
            own = own & (i >= 0) & (i < local[a])
            idx.append(jnp.clip(i, 0, local[a] - 1))
        axes = tuple(m for m in cfg["out_spec"] if m)
        return tuple(jax.lax.psum(jnp.where(own, y[tuple(idx)], 0.0), axes)
                     for y in (yr, yi))

    gather = jax.shard_map(gather, mesh=mesh,
                           in_specs=(out_spec, out_spec, P()),
                           out_specs=(P(), P()))

    @jax.jit
    def ft_step(u0r, u0i, t, p):
        c = -4.0 * np.pi ** 2 * alpha * t.astype(jnp.float32)
        f = jnp.exp(c * (k2[0][:, None, None] + k2[1][None, :, None]
                         + k2[2][None, None, :]))
        yr, yi = forward(u0r * f, -(u0i * f))
        pr, pi = gather(yr, yi, p)
        return pr, -pi

    ts = [jax.device_put(np.int32(t), NamedSharding(mesh, P()))
          for t in range(1, niter + 1)]
    ft_step(u0r, u0i, ts[0], pts_dev)[0].block_until_ready()
    harness.steady()

    outs, it = [], 0
    span = jax.profiler.TraceAnnotation
    with harness.Window(cell.trace, cell.tmp, counter) as win:
        end = win.start + cell.seconds
        while True:
            t = it % niter + 1
            with span("bench.step"):
                out = ft_step(u0r, u0i, ts[t - 1], pts_dev)
            with span("bench.wait"):
                out[0].block_until_ready()
            outs.append((t, out))
            it += 1
            if time.monotonic() >= end:
                break
    peak = harness.memory_peak(devices)

    checked = _samples(cell, sorted({t for t, _ in outs}))
    got = [(t, np.asarray(o[0])[sel] + 1j * np.asarray(o[1])[sel])
           for t, o in outs if t in checked]
    del u0r, u0i, outs
    xr, xi = (np.asarray(a) for a in make(key))
    want = dict(zip(checked, cell.reference.points(xr, xi, checked, alpha,
                                                   pts[sel])))
    del xr, xi
    err = max(check.rel_l2(g[None], want[t][None]) for t, g in got)
    ops, nbytes = work.c2c_work(shape)
    nbytes += work.evolve_bytes(shape)
    return harness.Outcome(
        window_start=win.start,
        metrics={"ft_s_per_iter": win.seconds / it},
        attempted=it, failed=0,
        checks=[("max_rel_l2", err, cfg["check"]["max_rel_l2"])],
        compiles_in_window=win.compiles, memory_peak_bytes=peak,
        trace_file=win.trace_file,
        layer={"step": {"ops": ops / cell.chips,
                        "bytes": nbytes / cell.chips, "calls": it,
                        "module": "jit_ft_step"},
               "checked_t": checked})
