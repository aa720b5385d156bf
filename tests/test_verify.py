"""ABFT silent-corruption defense (DESIGN.md §13, core/resilience/verify.py).

Contract under test: the ``corrupt`` fault kind perturbs values at
post-CRC checkpoints where every byte-integrity layer has already signed
off; the verification modes ("parseval" per-member energy, "abft"
checksum-row-per-launch) are the only defense, detections raise
`SilentCorruption` (an IOError, hence retryable by the ONE RetryPolicy),
and the quarantined unit recomputes to the bitwise-clean answer. The big
storm/overhead gate is benchmarks/bench_verify.py (BENCH_verify.json).
"""

import threading
from contextlib import nullcontext

import numpy as np
import pytest

from repro.core.pipeline import (BlockStore, JobConfig, MapOnlyJob,
                                 SegmentFFTTransform)
from repro.core.pipeline.records import segment_block_bytes
from repro.core.resilience import (FaultInjector, FaultPlan, RetryPolicy,
                                   clear_events, events)
from repro.core.resilience import verify as abft
from repro.core.resilience.faults import (KINDS, FaultRule, corrupt_salt,
                                          perturb_array)
import repro.fft as fft_api

pytestmark = pytest.mark.verify

FFT_LEN = 128
SEG_PER_BLOCK = 16


# ---------------------------------------------------------------------------
# invariant checkers


def test_check_mode_accepts_known_rejects_unknown():
    for m in abft.VERIFY_MODES:
        assert abft.check_mode(m) == m
    with pytest.raises(ValueError, match="verify mode"):
        abft.check_mode("checksum")


def test_tolerances_derive_from_eps_and_depth():
    # deeper transforms accumulate more rounding -> wider tolerance
    assert abft.parseval_rtol(1 << 20) > abft.parseval_rtol(1 << 4)
    # f64 eps is ~2^-29 of f32's
    assert abft.parseval_rtol(1 << 10, "f64") < abft.parseval_rtol(1 << 10)
    # the batch reduction widens the checksum tolerance with sqrt(rows)
    assert abft.abft_rtol(FFT_LEN, 64) > abft.abft_rtol(FFT_LEN, 4) \
        > abft.parseval_rtol(FFT_LEN)


def test_energy_squares_native_accumulates_float64(rng):
    a = rng.standard_normal(1000).astype(np.float32)
    b = rng.standard_normal(500).astype(np.float32)
    # exact contract: squares in the operand dtype (so re-summing the
    # same values is reproducible), accumulation in float64
    want = float(np.sum(np.square(a), dtype=np.float64)
                 + np.sum(np.square(b), dtype=np.float64))
    assert abft.energy(a, b) == want
    # and still within f32 eps of the all-float64 reference
    ref = float(np.sum(np.square(a, dtype=np.float64))
                + np.sum(np.square(b, dtype=np.float64)))
    assert abft.energy(a, b) == pytest.approx(ref, rel=1e-6)


def test_energy_onesided_matches_full_spectrum(rng):
    x = rng.standard_normal(FFT_LEN)
    full = abft.energy(np.fft.fft(x).real, np.fft.fft(x).imag)
    half = np.fft.rfft(x)
    assert abft.energy_onesided(half.real, half.imag, FFT_LEN) == \
        pytest.approx(full, rel=1e-9)


def _planar_batch(rng, rows):
    return (rng.standard_normal((rows, FFT_LEN)).astype(np.float32),
            rng.standard_normal((rows, FFT_LEN)).astype(np.float32))


def test_parseval_passes_honest_fft_catches_perturbation(rng):
    xr, xi = _planar_batch(rng, 4)
    p = fft_api.plan(kind="c2c", n=FFT_LEN, batch_shape=(4,), impl="ref")
    yr, yi = (np.asarray(a) for a in p.execute(xr, xi))
    e_in = abft.energy(xr, xi)
    abft.check_parseval(e_in, abft.energy(yr, yi), FFT_LEN,
                        site="stream.realize")  # honest: no raise
    bad = perturb_array(yr.copy(), 0.5, corrupt_salt("stream.realize", 0))
    clear_events()
    with pytest.raises(abft.SilentCorruption) as exc:
        abft.check_parseval(e_in, abft.energy(bad, yi), FFT_LEN,
                            site="stream.realize", index=3)
    assert exc.value.site == "stream.realize" and exc.value.index == 3
    evs = events("verify_failed")
    assert len(evs) == 1 and evs[0]["invariant"] == "parseval"


def test_checksum_row_passes_linearity_catches_any_row(rng):
    rows = 4
    xr, xi = _planar_batch(rng, rows)
    w = abft.checksum_weights(rows, seed=rows)
    ops = abft.add_checksum_row([xr, xi], w)
    p = fft_api.plan(kind="c2c", n=FFT_LEN, batch_shape=(rows + 1,),
                     impl="ref")
    host = [np.asarray(a) for a in p.execute(*ops)]
    abft.check_checksum(host, w, FFT_LEN, site="serve.execute")  # honest
    # a perturbed MEMBER row breaks the combination...
    bad = [host[0].copy(), host[1]]
    bad[0][2] = perturb_array(bad[0][2].copy(), 0.5,
                              corrupt_salt("serve.execute", 2))
    with pytest.raises(abft.SilentCorruption):
        abft.check_checksum(bad, w, FFT_LEN, site="serve.execute")
    # ...and so does a perturbed CHECKSUM row itself
    bad = [host[0].copy(), host[1]]
    bad[0][rows] = perturb_array(bad[0][rows].copy(), 0.5,
                                 corrupt_salt("serve.execute", rows))
    with pytest.raises(abft.SilentCorruption):
        abft.check_checksum(bad, w, FFT_LEN, site="serve.execute")


def test_checksum_weights_deterministic_and_bounded():
    w1, w2 = abft.checksum_weights(32, seed=5), abft.checksum_weights(32, 5)
    assert np.array_equal(w1, w2) and w1.dtype == np.float32
    assert float(w1.min()) >= 0.5 and float(w1.max()) <= 1.5
    assert not np.array_equal(w1, abft.checksum_weights(32, seed=6))


def test_silent_corruption_is_retryable_ioerror():
    err = abft.SilentCorruption("x", site="serve.execute", index=1)
    assert isinstance(err, IOError)
    # the blockstore/stream policies restrict retryable to I/O classes;
    # SilentCorruption must still qualify so quarantine == retry
    assert RetryPolicy(retryable=(IOError, OSError)).retryable_exc(err)


def test_cost_model_off_parseval_abft():
    assert abft.verify_flops("off", FFT_LEN, 8) == 0
    assert abft.verify_hbm_bytes("off", FFT_LEN, 8) == 0
    assert abft.verify_flops("parseval", FFT_LEN, 0) == 0
    # abft's combination+residual passes cost more flops than the energy
    # reductions, on the same two extra plane reads
    assert abft.verify_flops("abft", FFT_LEN, 8) > \
        abft.verify_flops("parseval", FFT_LEN, 8) > 0
    assert abft.verify_hbm_bytes("abft", FFT_LEN, 8) == \
        abft.verify_hbm_bytes("parseval", FFT_LEN, 8) > 0


# ---------------------------------------------------------------------------
# corrupt fault rules: schedule, spec grammar, determinism


def test_corrupt_rule_validation():
    assert KINDS == ("raise", "corrupt")
    with pytest.raises(ValueError, match="kind"):
        FaultRule("stream.realize", 0, kind="flip")
    with pytest.raises(ValueError, match="scale"):
        FaultRule("stream.realize", 0, kind="corrupt", scale=0.0)
    with pytest.raises(ValueError, match="kind"):
        FaultPlan.random(0, 4, kind="flip")


def test_corrupt_parse_and_to_spec_roundtrip():
    plan = FaultPlan.parse(
        "seed=7,rate=0.5,sites=stream.realize+serve.execute,kind=corrupt",
        num_blocks=16)
    assert plan.rules and all(r.kind == "corrupt" for r in plan.rules)
    assert all(0.25 <= r.scale <= 4.0 for r in plan.rules)
    # to_spec emits explicit rules (scales included): replays exactly,
    # independent of the parser's num_blocks
    again = FaultPlan.parse(plan.to_spec(), num_blocks=0)
    assert again.rules == plan.rules


def test_corrupt_storm_targets_match_raise_storm():
    """Same seed -> same (site, block) hit pattern for both kinds: a raise
    storm can be re-run as silent corruption without reshuffling."""
    sites = ("stream.realize", "serve.execute")
    for seed in (0, 7, 1407):
        hit = FaultPlan.random(seed, 32, sites=sites, rate=0.3)
        corr = FaultPlan.random(seed, 32, sites=sites, rate=0.3,
                                kind="corrupt")
        assert {(r.site, r.index) for r in hit.rules} == \
            {(r.site, r.index) for r in corr.rules}


def test_perturbation_deterministic_and_norm_relative(rng):
    a = rng.standard_normal(512).astype(np.float32)
    salt = corrupt_salt("stream.realize", 9)
    b1 = perturb_array(a.copy(), 1.0, salt)
    b2 = perturb_array(a.copy(), 1.0, salt)
    assert np.array_equal(b1, b2)               # pure function of salt
    assert not np.array_equal(b1, perturb_array(a.copy(), 1.0, salt + 1))
    # exactly one element moved, by O(scale * ||a||): provably above any
    # eps-derived tolerance regardless of n
    changed = np.flatnonzero(b1 != a)
    assert changed.size == 1
    delta = abs(float(b1[changed[0]] - a[changed[0]]))
    assert delta >= 0.5 * (1.0 + float(np.linalg.norm(a))) * 0.9


# ---------------------------------------------------------------------------
# end-to-end quarantine-and-recompute (small; the storm gate is the bench)


def _store(tmp_path, rng, blocks=4):
    sig = rng.standard_normal(
        (SEG_PER_BLOCK * blocks, FFT_LEN, 2)).astype(np.float32)
    store = BlockStore(tmp_path / "in",
                       block_bytes=segment_block_bytes(FFT_LEN,
                                                       SEG_PER_BLOCK))
    store.put_bytes(sig.tobytes())
    return store


def _stream_run(store, out_dir, injector, verify):
    cfg = JobConfig(readers=2, writers=2, coalesce=2, inflight=2,
                    speculation=False, max_retries=4, injector=injector)
    store.injector = injector
    job = MapOnlyJob(store, out_dir, config=cfg, pipelined=True,
                     transform=SegmentFFTTransform(FFT_LEN, impl="ref",
                                                   verify=verify))
    stats = job.run()
    job.merge(out_dir.parent / f"{out_dir.name}.bin")
    return stats, (out_dir.parent / f"{out_dir.name}.bin").read_bytes()


def test_stream_abft_detects_and_recovers_bitwise(tmp_path, rng):
    store = _store(tmp_path, rng)
    _, clean = _stream_run(store, tmp_path / "clean", None, "abft")

    storm = FaultPlan((FaultRule("stream.realize", 1, kind="corrupt",
                                 scale=2.0),))
    clear_events()
    inj = FaultInjector(storm)
    stats, got = _stream_run(store, tmp_path / "storm", inj, "abft")
    assert inj.total_corrupted == 1
    assert len(events("verify_failed")) >= 1
    assert stats.retries >= 1 and not stats.failed_blocks
    assert got == clean  # recompute restored the clean bytes

    # negative control: the same storm with verify off sails through every
    # byte check — wrong output, zero retries
    stats_off, off = _stream_run(store, tmp_path / "off",
                                 FaultInjector(storm), "off")
    assert off != clean and stats_off.retries == 0


def test_stream_parseval_quarantines_only_the_member(tmp_path, rng):
    store = _store(tmp_path, rng)
    _, clean = _stream_run(store, tmp_path / "clean", None, "parseval")
    clear_events()
    stats, got = _stream_run(
        store, tmp_path / "storm",
        FaultInjector(FaultPlan((FaultRule("stream.realize", 2,
                                           kind="corrupt"),))), "parseval")
    assert len(events("verify_failed")) == 1
    assert stats.retries == 1  # member-granular: one block requeued
    assert got == clean


def test_maponly_serial_verify_fn_catches_post_map_corruption(tmp_path, rng):
    from repro.launch.fft_job import parseval_verify_fn, serial_map_fn

    store = _store(tmp_path, rng)
    runs = iter(range(10))  # unique per-run dirs (id() reuses addresses)

    def run(injector, verify_fn):
        i = next(runs)
        cfg = JobConfig(workers=2, max_retries=4, injector=injector,
                        verify_fn=verify_fn)
        store.injector = injector
        job = MapOnlyJob(store, tmp_path / f"out{i}",
                         serial_map_fn(FFT_LEN, "ref",
                                       lambda stage: nullcontext()), cfg)
        stats = job.run()
        job.merge(tmp_path / f"m{i}.bin")
        return stats, (tmp_path / f"m{i}.bin").read_bytes()

    _, clean = run(None, None)
    storm = FaultPlan((FaultRule("maponly.attempt", 0, kind="corrupt"),))
    clear_events()
    stats, got = run(FaultInjector(storm), parseval_verify_fn(FFT_LEN))
    assert len(events("verify_failed")) == 1
    assert stats.retries >= 1 and got == clean
    # without the hook the corrupted bytes are written as-is
    stats_off, off = run(FaultInjector(storm), None)
    assert stats_off.retries == 0 and off != clean


def test_serve_abft_quarantines_group_and_recomputes(rng):
    from repro.serve import FftService, loadgen

    class _Shape:
        kind, n, rows = "c2c", FFT_LEN, 2

    reqs = [tuple(rng.standard_normal((2, FFT_LEN)).astype(np.float32)
                  for _ in range(2)) for _ in range(4)]
    storm = FaultPlan((FaultRule("serve.execute", 0, kind="corrupt"),))
    clear_events()
    svc = FftService(impl="ref", coalesce=2, injector=FaultInjector(storm),
                     verify="abft")
    tickets = [svc.submit("c2c", xr, xi) for xr, xi in reqs]
    for t in tickets:
        assert t.wait(60)
    svc.close(drain=True)
    assert svc.stats.corruption_detected >= 1
    # checksum failures cannot name the culprit: the whole coalesced
    # group quarantined, then every member recomputed clean
    assert svc.stats.corruption_recomputed >= 2
    assert all(t.error is None for t in tickets)
    for t, ops in zip(tickets, reqs):
        want = loadgen.oracle(_Shape, ops, impl="ref",
                              batch_rows=t.batch_rows)
        for g, w in zip(t.value, want):
            assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


# ---------------------------------------------------------------------------
# plan cache: verify is part of the key; counters stay exact under races


def test_verify_resolved_into_plan_cache_key():
    fft_api.clear_plan_cache()
    p_off = fft_api.plan(kind="c2c", n=FFT_LEN, batch_shape=(4,),
                         impl="ref")
    p_ver = fft_api.plan(kind="c2c", n=FFT_LEN, batch_shape=(4,),
                         impl="ref", verify="abft")
    assert p_off is not p_ver
    assert p_off.verify_flops == 0 and p_ver.verify_flops > 0
    assert p_ver.verify_overhead > 0.0
    assert fft_api.plan(kind="c2c", n=FFT_LEN, batch_shape=(4,),
                        impl="ref", verify="abft") is p_ver
    with pytest.raises(ValueError, match="verify"):
        fft_api.plan(kind="c2c", n=FFT_LEN, batch_shape=(4,),
                     impl="ref", verify="bogus")


def test_plan_cache_counters_exact_under_concurrent_plan_calls():
    """The serve batcher and a stream dispatcher plan concurrently in one
    process: cache counters must reconcile exactly (hits + misses ==
    calls, one miss per distinct resolved spec) — the get-or-build is a
    single critical section, not check-then-insert."""
    fft_api.clear_plan_cache()
    # the serving mix: two batch geometries x two verify modes
    keys = [dict(kind="c2c", n=FFT_LEN, batch_shape=(rows,), impl="ref",
                 verify=v)
            for rows in (4, 9) for v in ("off", "abft")]
    iters, nthreads = 8, 6
    start = threading.Barrier(nthreads)
    errors = []

    def worker(tid):
        try:
            start.wait()
            for i in range(iters):
                kw = keys[(tid + i) % len(keys)]
                p = fft_api.plan(**kw)
                assert p.verify_flops == (0 if kw["verify"] == "off"
                                          else abft.verify_flops(
                                              "abft", FFT_LEN,
                                              kw["batch_shape"][0]))
        except BaseException as e:  # surface failures from threads
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    info = fft_api.cache_info()
    calls = iters * nthreads
    assert info["entries"] == len(keys)
    assert info["misses"] == len(keys)  # each spec built exactly once
    assert info["hits"] == calls - len(keys)
    assert info["invalidations"] == 0
