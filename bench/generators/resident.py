"""Device-resident batches: the job's device step without the host.

Set-up makes one batch of planar float32 operands on the device from the
seed, plans the transform the configuration names and calls it until
every program it uses is loaded. The window then calls
``plan.execute_async`` on those operands back to back, keeping
``inflight`` calls queued on the device: when the queue is full it waits
for the oldest. Nothing is generated or allocated on the host in the
window. When ``seconds`` have passed it waits for every call; the rate is
all points of all calls over the whole window.

Checked: rows drawn from the seed, of calls drawn from the seed (gathered
on the device when the call is dispatched) and of the last ``inflight``
calls, against the configuration's float64 reference.

Traffic parameters: ``inflight`` (calls queued on the device),
``sample_rows`` (rows checked per sampled call), ``sample_call_ranges``
(one call is drawn from each ``[lo, hi)``).
"""

from __future__ import annotations

import math
import time
from collections import deque

import numpy as np

from bench import check, harness, work


def _plan(cfg: dict):
    import repro.fft as fft_api
    t = cfg["transform"]
    if t["kind"] != "c2c":
        raise ValueError(f"the resident generator runs c2c transforms, not "
                         f"{t['kind']!r}")
    return fft_api.plan(kind="c2c", shape=tuple(t["shape"]),
                        batch_shape=(t["batch"],), impl=cfg["impl"])


def _inputs(cell: harness.Cell):
    """The operands on the device, the rows checked and the calls whose
    rows are taken, all from the seed."""
    import jax
    import jax.numpy as jnp
    t = cell.config["transform"]
    batch = int(t["batch"])
    operand = (batch, *t["shape"])

    @jax.jit
    def make(key):
        kr, ki = jax.random.split(key)
        return (jax.random.normal(kr, operand, jnp.float32),
                jax.random.normal(ki, operand, jnp.float32))

    xr, xi = make(harness.jax_key(cell.seed))
    rng = harness.numpy_rng(cell.seed, 1)
    rows = np.sort(rng.choice(batch, min(batch,
                                         int(cell.traffic["sample_rows"])),
                              replace=False))
    sampled = {int(rng.integers(lo, hi)) for lo, hi in
               cell.traffic["sample_call_ranges"]}
    return xr, xi, rows, sampled


def control(cell: harness.Cell) -> list:
    """The check with the bf16 x3 DFT (``Precision.HIGH``) in the
    program's place, over the whole batch."""
    xr, xi, rows, _ = _inputs(cell)
    yr, yi = check.control_dft(xr, xi)
    got = np.asarray(yr[rows]) + 1j * np.asarray(yi[rows])
    want = cell.reference.spectra(np.asarray(xr[rows]), np.asarray(xi[rows]))
    return [("max_rel_l2", check.rel_l2(got, want),
             cell.config["check"]["max_rel_l2"])]


def run(cell: harness.Cell, counter: harness.CompileCounter,
        execute=None) -> harness.Outcome:
    """``execute`` replaces ``plan.execute_async`` (tests plant faults)."""
    import jax
    import jax.numpy as jnp

    cfg, tr = cell.config, cell.traffic
    t = cfg["transform"]
    shape, batch = tuple(t["shape"]), int(t["batch"])
    depth = int(tr["inflight"])
    plan = _plan(cfg)
    call = execute or plan.execute_async
    xr, xi, rows, sampled = _inputs(cell)
    rows_dev = jnp.asarray(rows)
    take = jax.jit(lambda yr, yi, r: (yr[r], yi[r]))

    # warm-up: every program the window runs, called as the window calls it
    q = deque(call(xr, xi) for _ in range(depth + 1))
    take(*q[0], rows_dev)[0].block_until_ready()
    for out in q:
        out[0].block_until_ready()
    q.clear()
    harness.steady()

    taken = []
    calls = 0
    span = jax.profiler.TraceAnnotation
    with harness.Window(cell.trace, cell.tmp, counter) as win:
        end = win.start + cell.seconds
        while True:
            with span("bench.execute_async"):
                out = call(xr, xi)
            if calls in sampled:
                with span("bench.take"):
                    taken.append((calls, take(*out, rows_dev)))
            q.append(out)
            calls += 1
            if len(q) >= depth:
                with span("bench.wait_oldest"):
                    q.popleft()[0].block_until_ready()
            if time.monotonic() >= end:
                break
        with span("bench.drain"):
            for out in q:
                out[0].block_until_ready()
                out[1].block_until_ready()
    points = calls * batch * math.prod(shape)
    peak = harness.memory_peak(jax.local_devices()[:cell.chips])
    taken += [(calls - len(q) + i, take(*out, rows_dev))
              for i, out in enumerate(q)]
    got = [(i, np.asarray(yr) + 1j * np.asarray(yi)) for i, (yr, yi) in taken]
    xs = take(xr, xi, rows_dev)
    x_r, x_i = np.asarray(xs[0]), np.asarray(xs[1])
    del q, out, xr, xi, xs, taken
    want = cell.reference.spectra(x_r, x_i)
    err = max(check.rel_l2(g, want) for _, g in got)
    ops, nbytes = work.c2c_work(shape, batch)
    return harness.Outcome(
        window_start=win.start,
        metrics={"gpoints_per_s": points / win.seconds / 1e9},
        attempted=calls, failed=0,
        checks=[("max_rel_l2", err, cfg["check"]["max_rel_l2"])],
        compiles_in_window=win.compiles, memory_peak_bytes=peak,
        trace_file=win.trace_file,
        layer={"step": {"ops": ops, "bytes": nbytes, "calls": calls,
                        "module": "jit_counted"},
               "checked_calls": [i for i, _ in got]})
