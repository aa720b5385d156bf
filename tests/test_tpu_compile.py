"""Compile the main-path kernels for a described v5e chip (nothing runs).

Each test lowers and compiles one entry point with ``interpret=False``
for a TPU v5e that is described, not attached, and checks that the
compiled program holds the Pallas kernel (``tpu_custom_call``). This is
what the chip's compiler would refuse — unsupported shape casts, blocks
not aligned to the (8, 128) tiling, more VMEM than a core has — at no
chip time. The topology is described inside a module fixture, so only
the worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fft import executors
from repro.kernels.fft.matfft import matfft, matfft_cols, rfft_leaf


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [1024, 16384])
def test_matfft_compiles(one_chip, n):
    text = _compile_text(lambda a, b: matfft(a, b, interpret=False),
                         one_chip, (256, n), (256, n))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("out_major", ["row", "col"])
def test_matfft_cols_compiles_at_max_leaf(one_chip, out_major):
    text = _compile_text(
        lambda a, b: matfft_cols(a, b, out_major=out_major, interpret=False),
        one_chip, (2, 16384, 256), (2, 16384, 256))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [1024, 32768])
def test_rfft_leaf_compiles(one_chip, n):
    text = _compile_text(lambda x: rfft_leaf(x, interpret=False),
                         one_chip, (256, n))
    assert "tpu_custom_call" in text


def test_matfft_global_twiddle_compiles(one_chip):
    def f(a, b):
        return matfft(a, b, global_twiddle=(1 << 32, jnp.int32(3)),
                      interpret=False)

    text = _compile_text(f, one_chip, (256, 16384), (256, 16384))
    assert "tpu_custom_call" in text


def test_level1_fft_compiles(one_chip):
    text = _compile_text(
        lambda a, b: executors.fft(a, b, interpret=False),
        one_chip, (4, 1 << 20), (4, 1 << 20))
    assert text.count("tpu_custom_call") >= 2
