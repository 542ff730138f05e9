"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse
(unaligned tiles, too much VMEM), which interpret mode cannot show. The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this
file.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import parity as par


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *structs) -> str:
    return jax.jit(fn).lower(*structs).compile().as_text()


@pytest.mark.parametrize("K,N,block", [
    (4, 262144, 4096),        # one 1 MiB raid5 stripe-round of 4 units
    (4, 1 << 20, 1 << 14),
    (3, 1000, 1000),          # ragged: the whole row is one block
    (4, 5000, 4096),          # ragged tail, zero-padded to the block
])
def test_parity_kernels_compile_for_v5e(one_chip, K, N, block):
    blocks = jax.ShapeDtypeStruct((K, N), jnp.int32, sharding=one_chip)
    survivors = jax.ShapeDtypeStruct((K - 1, N), jnp.int32,
                                     sharding=one_chip)
    parity = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    enc = _compiled_text(
        partial(par.xor_parity, block=block, interpret=False), blocks)
    dec = _compiled_text(
        partial(par.reconstruct, block=block, interpret=False),
        survivors, parity)
    assert "tpu_custom_call" in enc
    assert "tpu_custom_call" in dec


def test_flash_attention_forward_compiles_for_v5e(one_chip):
    q = jax.ShapeDtypeStruct((1, 32, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.bfloat16,
                              sharding=one_chip)
    text = _compiled_text(
        partial(fa.flash_attention, causal=True, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text
