"""Smoke target for the plan-and-execute facade.

    PYTHONPATH=src python -m repro.fft.selftest

Plans + executes c2c and r2c at every placement the container can host —
leaf (level 0), four-step (level 1), segmented and distributed over an
8-device CPU mesh — in interpret mode, checks each against the numpy
oracle, and verifies the plan cache never retraces. The distributed case
runs BOTH exchange engines (overlap="off" monolithic all_to_alls and an
overlapped ppermute pipeline) and asserts their outputs are bitwise
identical. The 2-D cases cover local fft2/rfft2 against numpy and the
distributed pencil placement (one exchange leg) in both overlap modes,
with a bitwise cross-check between the local and distributed results
(matched kernel tiles -> identical GEMMs). Exit code 0 = all pass. Wired
into test.sh and the CI workflow as the facade's cheap end-to-end gate.
"""

import os
import sys

# a CPU smoke on forced host devices: never claim an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft_api  # noqa: E402
from repro import compat  # noqa: E402

TOL = 5e-6


def _rel_err(got_r, got_i, want):
    got = np.asarray(got_r) + 1j * np.asarray(got_i)
    scale = np.abs(want).max() or 1.0
    return float(np.abs(got - want).max() / scale)


def _check(name: str, err: float, plan) -> bool:
    retrace_ok = plan.trace_counts["forward"] == 1
    ok = err < TOL and retrace_ok
    print(f"selftest {name:<24} {'OK' if ok else 'FAIL'} "
          f"(err={err:.2e}, traces={plan.trace_counts['forward']})")
    return ok


def main() -> int:
    rng = np.random.default_rng(0)
    mesh = compat.make_mesh((jax.device_count(),), ("data",))
    ok = True

    cases = [
        # (label, n, batch, mesh, placement)
        ("leaf", 1024, (4,), None, "local"),
        ("four_step", 1 << 15, (2,), None, "local"),
        ("segmented", 512, (16,), mesh, "segmented"),
    ]
    for label, n, batch, m, placement in cases:
        xr = rng.standard_normal((*batch, n)).astype(np.float32)
        xi = rng.standard_normal((*batch, n)).astype(np.float32)

        p = fft_api.plan(kind="c2c", n=n, batch_shape=batch, mesh=m,
                         placement=placement, interpret=True)
        yr, yi = p.execute(jnp.asarray(xr), jnp.asarray(xi))
        p.execute(jnp.asarray(xr), jnp.asarray(xi))  # must not retrace
        ok &= _check(f"c2c/{label}", _rel_err(yr, yi, np.fft.fft(xr + 1j * xi)),
                     p)

        # r2c at the same placement; four_step = the level-1 half-length
        # regime (n such that n//2 > MAX_LEAF exercises the host untangle)
        rn = 2 * n if label == "four_step" else n
        x = rng.standard_normal((*batch, rn)).astype(np.float32)
        pr = fft_api.plan(kind="r2c", n=rn, batch_shape=batch, mesh=m,
                          placement=placement, interpret=True)
        sr, si = pr.execute_real(jnp.asarray(x))
        pr.execute_real(jnp.asarray(x))
        ok &= _check(f"r2c/{label}", _rel_err(sr, si, np.fft.rfft(x)), pr)

    # distributed: cross-device four-step, both exchange engines. The
    # overlapped ppermute pipeline must match the monolithic all_to_all
    # path bit for bit — same kernels, the exchange is pure data movement.
    nd = 4096
    xr = rng.standard_normal(nd).astype(np.float32)
    xi = rng.standard_normal(nd).astype(np.float32)
    want = np.fft.fft(xr + 1j * xi)
    p_off = fft_api.plan(kind="c2c", n=nd, mesh=mesh,
                         placement="distributed", overlap="off",
                         interpret=True)
    yr0, yi0 = p_off.execute(jnp.asarray(xr), jnp.asarray(xi))
    p_off.execute(jnp.asarray(xr), jnp.asarray(xi))
    ok &= _check("c2c/dist_off", _rel_err(yr0, yi0, want), p_off)

    p_on = fft_api.plan(kind="c2c", n=nd, mesh=mesh,
                        placement="distributed", overlap=4, interpret=True)
    yr1, yi1 = p_on.execute(jnp.asarray(xr), jnp.asarray(xi))
    p_on.execute(jnp.asarray(xr), jnp.asarray(xi))
    ok &= _check("c2c/dist_overlap4", _rel_err(yr1, yi1, want), p_on)
    bitwise = bool((np.asarray(yr1) == np.asarray(yr0)).all()
                   and (np.asarray(yi1) == np.asarray(yi0)).all())
    print(f"selftest dist overlap==off bitwise     "
          f"{'OK' if bitwise else 'FAIL'} "
          f"(exposed {p_on.exposed_collective_bytes} of "
          f"{p_on.collective_bytes} collective bytes)")
    ok &= bitwise

    # ---- 2-D: local c2c + r2c against numpy ----
    n0, n1 = 64, 64
    ir = rng.standard_normal((n0, n1)).astype(np.float32)
    ii = rng.standard_normal((n0, n1)).astype(np.float32)
    want2 = np.fft.fft2(ir + 1j * ii)
    # batch_tile = n1/D matches the distributed shard's kernel tiles, so
    # the local and pencil results below are bitwise-comparable
    bt = n1 // jax.device_count()
    p2 = fft_api.plan(kind="c2c", shape=(n0, n1), interpret=True,
                      batch_tile=bt)
    lr, li = p2.execute(jnp.asarray(ir), jnp.asarray(ii))
    p2.execute(jnp.asarray(ir), jnp.asarray(ii))
    ok &= _check("c2c/fft2_local", _rel_err(lr, li, want2), p2)

    p2r = fft_api.plan(kind="r2c", shape=(n0, n1), interpret=True)
    sr2, si2 = p2r.execute_real(jnp.asarray(ir))
    p2r.execute_real(jnp.asarray(ir))
    ok &= _check("r2c/rfft2_local", _rel_err(sr2, si2, np.fft.rfft2(ir)),
                 p2r)

    # ---- 2-D: distributed pencil (ONE exchange leg), both engines ----
    p2_off = fft_api.plan(kind="c2c", shape=(n0, n1), mesh=mesh,
                          placement="distributed", overlap="off",
                          interpret=True, batch_tile=bt)
    dr, di = p2_off.execute(jnp.asarray(ir), jnp.asarray(ii))
    p2_off.execute(jnp.asarray(ir), jnp.asarray(ii))
    ok &= _check("c2c/pencil_off", _rel_err(dr, di, want2), p2_off)
    one_leg = p2_off.dist.n_exchanges == 1
    print(f"selftest pencil exchange legs         "
          f"{'OK' if one_leg else 'FAIL'} "
          f"({p2_off.dist.n_exchanges} leg, "
          f"{p2_off.collective_bytes} collective bytes)")
    ok &= one_leg

    p2_on = fft_api.plan(kind="c2c", shape=(n0, n1), mesh=mesh,
                         placement="distributed", overlap=4,
                         interpret=True, batch_tile=bt)
    er2, ei2 = p2_on.execute(jnp.asarray(ir), jnp.asarray(ii))
    p2_on.execute(jnp.asarray(ir), jnp.asarray(ii))
    ok &= _check("c2c/pencil_overlap4", _rel_err(er2, ei2, want2), p2_on)
    bitwise2 = bool((np.asarray(er2) == np.asarray(dr)).all()
                    and (np.asarray(ei2) == np.asarray(di)).all())
    print(f"selftest pencil overlap==off bitwise   "
          f"{'OK' if bitwise2 else 'FAIL'}")
    ok &= bitwise2
    bitwise_ld = bool((np.asarray(dr) == np.asarray(lr)).all()
                      and (np.asarray(di) == np.asarray(li)).all())
    print(f"selftest pencil==local bitwise         "
          f"{'OK' if bitwise_ld else 'FAIL'} (matched tiles)")
    ok &= bitwise_ld

    # ---- r2c pencil: packed half-width volume through the exchange ----
    pr2 = fft_api.plan(kind="r2c", shape=(n0, 4 * n1), mesh=mesh,
                       placement="distributed", overlap="off",
                       interpret=True)
    xrr = rng.standard_normal((n0, 4 * n1)).astype(np.float32)
    hr, hi = pr2.execute_real(jnp.asarray(xrr))
    pr2.execute_real(jnp.asarray(xrr))
    ok &= _check("r2c/pencil", _rel_err(hr, hi, np.fft.rfft2(xrr)), pr2)

    # ---- 3-D pencil: one mesh axis per sharded axis, TWO exchange legs
    d = jax.device_count()
    if d >= 8 and d % 4 == 0:
        mesh3 = compat.make_mesh((4, d // 4), ("data", "model"))
        s3 = (16, 32, 64)
        vr = rng.standard_normal(s3).astype(np.float32)
        vi = rng.standard_normal(s3).astype(np.float32)
        p3 = fft_api.plan(kind="c2c", shape=s3, mesh=mesh3,
                          placement="distributed", overlap="off",
                          interpret=True)
        wr, wi = p3.execute(jnp.asarray(vr), jnp.asarray(vi))
        p3.execute(jnp.asarray(vr), jnp.asarray(vi))
        ok &= _check("c2c/pencil3d",
                     _rel_err(wr, wi, np.fft.fftn(vr + 1j * vi)), p3)
        two_legs = p3.dist.n_exchanges == 2
        print(f"selftest pencil3d exchange legs       "
              f"{'OK' if two_legs else 'FAIL'} "
              f"({p3.dist.n_exchanges} legs, per-leg "
              f"{list(p3.per_leg_collective_bytes)} bytes)")
        ok &= two_legs

    info = fft_api.cache_info()
    print(f"selftest plan cache: {info['misses']} built, "
          f"{info['hits']} hits")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
