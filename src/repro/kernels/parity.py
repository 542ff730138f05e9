"""XOR-parity (RAID-5 style erasure) Pallas TPU kernel (paper ch. 15:
Redundant Object Storage Targets — "a mirroring OBD driver ... other
mechanisms for use in an archive").

Checkpoint stripes are erasure-coded before hitting the OSTs: P = XOR of
the K data stripes; any single lost stripe (dead OST) is reconstructed as
XOR of the survivors + P. The compute is pure VPU lane work: int32 lanes,
(K, N) -> (N,), tiled over N so each tile's working set (K x block + block)
sits in VMEM.

TPU adaptation: a GPU implementation would coalesce over warps; here the
natural layout is (8, 128)-aligned int32 tiles and a grid over columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _xor_kernel(x_ref, o_ref):
    blk = x_ref[...]                       # (K, block) int32
    K = blk.shape[0]
    acc = blk[0]
    for i in range(1, K):                  # K is small + static: unrolled
        acc = jnp.bitwise_xor(acc, blk[i])
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xor_parity(blocks: jax.Array, *, block: int = 4096,
               interpret: bool = False) -> jax.Array:
    """blocks (K, N) int32 -> parity (N,) int32.

    N need not be a multiple of `block`: the grid must tile N evenly, so
    a ragged tail is zero-padded up to the next block boundary before the
    call (0 is the XOR identity — padding never changes the parity) and
    the pad lanes are sliced back off the result. Shapes are static, so
    the pad amount is resolved at trace time (one compiled kernel per
    distinct padded shape, exactly like the unpadded path)."""
    K, N = blocks.shape
    block = min(block, N)
    padded = -(-N // block) * block
    if padded != N:
        blocks = jnp.pad(blocks, ((0, 0), (0, padded - N)))
    out = pl.pallas_call(
        _xor_kernel,
        grid=(padded // block,),
        in_specs=[pl.BlockSpec((K, block), lambda j: (0, j))],
        out_specs=pl.BlockSpec((block,), lambda j: (j,)),
        out_shape=jax.ShapeDtypeStruct((padded,), jnp.int32),
        interpret=interpret,
        name="xor_parity",
    )(blocks)
    return out[:N] if padded != N else out


def reconstruct(survivors: jax.Array, parity: jax.Array, *,
                block: int = 4096, interpret: bool = False) -> jax.Array:
    """Recover the one missing stripe: XOR(survivors, parity)."""
    stacked = jnp.concatenate([survivors, parity[None]], axis=0)
    return xor_parity(stacked, block=block, interpret=interpret)
