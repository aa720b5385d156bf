# NOTE: no XLA_FLAGS here on purpose — tests must see the host's real
# single CPU device. Only launch/dryrun.py (never imported by tests)
# forces the 512-device count.
import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection runs over full pipelined jobs "
        "(deterministic; gated in test.sh/CI alongside bench_chaos.py)")
    config.addinivalue_line(
        "markers",
        "outofcore: streamed out-of-core FFT runs over a real on-disk "
        "BlockStore (small sizes; the big gate is bench_outofcore.py)")
    config.addinivalue_line(
        "markers",
        "serve: FFT-as-a-service front-end tests (admission control, "
        "dynamic batching, deadlines; the load gate is bench_serve.py)")
    config.addinivalue_line(
        "markers",
        "verify: ABFT silent-corruption defense tests (invariant checks, "
        "corrupt fault rules, quarantine-and-recompute; the storm gate "
        "is bench_verify.py)")
    config.addinivalue_line(
        "markers",
        "tune: measuring-autotuner and persistent-wisdom tests "
        "(determinism, wisdom round-trips, corrupt-file degradation; "
        "the measured-vs-analytic gate is bench_tune.py)")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
