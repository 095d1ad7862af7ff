"""The main-path Pallas kernels compile for a TPU v5e at the paper's size.

No chip is needed: the TPU compiler compiles for a described v5e:2x2
topology. These tests catch what interpret mode cannot — block shapes
Mosaic refuses, operations it cannot legalize — and assert that the
compiled HLO holds the kernel (`tpu_custom_call`), not an interpreted
copy of it. The topology is described inside a fixture, never while a
module is imported, so every test worker collects the same tests and
only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ring_wavg.kernel import ring_accum_pallas
from repro.kernels.robust_avg.kernel import trimmed_wavg_pallas
from repro.kernels.wavg.kernel import BLOCK_N, wavg_pallas

# The paper discriminator's 2,765,568 parameters, padded to BLOCK_N.
PAPER_BLOCKS = -(-2_765_568 // BLOCK_N)
PAPER_N = PAPER_BLOCKS * BLOCK_N


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k", [10, 4])
def test_wavg_compiles_at_paper_payload(one_chip, k):
    hlo = compiled_hlo(wavg_pallas, one_chip,
                       ((k, PAPER_N), jnp.float32), ((k,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_trimmed_wavg_compiles_at_paper_payload(one_chip):
    hlo = compiled_hlo(lambda x, w: trimmed_wavg_pallas(x, w, trim=1),
                       one_chip, ((10, PAPER_N), jnp.float32),
                       ((10,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("wire", [jnp.int16, jnp.float32])
def test_ring_accum_compiles_at_paper_payload(one_chip, wire):
    hlo = compiled_hlo(ring_accum_pallas, one_chip,
                       ((PAPER_BLOCKS, BLOCK_N), jnp.float32),
                       ((PAPER_BLOCKS, BLOCK_N), wire),
                       ((PAPER_BLOCKS,), jnp.float32))
    assert "tpu_custom_call" in hlo
