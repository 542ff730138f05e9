"""Public jit'd wrappers for the Pallas kernels.

On the CPU backend the kernels run in interpret mode (the kernel body is
executed op-by-op for correctness); on TPU they compile for real. Any
other backend is an error. Callers use these wrappers and never touch
`interpret` directly.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import metrics
from repro.kernels import flash_attention as _fa
from repro.kernels import parity as _par


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on tpu (or interpreted on "
                           f"cpu), not on {backend!r}")
    return backend == "cpu"


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    block_q=128, block_k=128):
    block_q = min(block_q, q.shape[2])
    block_k = min(block_k, k.shape[2])
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


def _gmm_tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 up to `cap` that divides `dim`, or the
    whole dimension where none does."""
    return next((t for t in range(cap, 0, -128) if dim % t == 0), dim)


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    # rows in tiles of 256 (each expert's group wastes at most one partly
    # filled tile); contraction and output tiles of at most 1152 columns,
    # which keeps the rhs, lhs, output and f32 accumulator tiles of the
    # forward (gmm) and the weight gradient (tgmm) in scoped VMEM
    return _gmm_tile(m, 256), _gmm_tile(k, 1152), _gmm_tile(n, 1152)


def gmm(lhs, rhs, group_sizes, *, out_dtype):
    """Grouped matmul (JAX's Pallas megablox kernel, with its backward):
    rows of `lhs` (m, k), sorted by group, times their group's `rhs`
    (g, k, n). `group_sizes` may name groups beyond the g of `rhs`: their
    rows are neither computed nor read, and come out as zeros."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb
    return mb.gmm(lhs, rhs, group_sizes, out_dtype, _gmm_tiling, None,
                  None, False, _interpret())


def xor_parity(blocks, *, block=4096):
    block = min(block, blocks.shape[1])
    return _par.xor_parity(blocks, block=block, interpret=_interpret())


def reconstruct(survivors, parity, *, block=4096):
    block = min(block, parity.shape[0])
    return _par.reconstruct(survivors, parity, block=block,
                            interpret=_interpret())


# ------------------------------------------------------- byte helpers
@metrics.spanned("kernel.parity")
def parity_bytes(chunks: list[bytes]) -> bytes:
    """XOR parity over equal-length byte chunks (pads the tail). Its span's
    self time is the packing and unpacking on the host."""
    n = max(len(c) for c in chunks)
    n4 = -(-n // 4) * 4
    arr = np.zeros((len(chunks), n4 // 4), np.int32)
    for i, c in enumerate(chunks):
        buf = np.zeros(n4, np.uint8)
        buf[:len(c)] = np.frombuffer(c, np.uint8)
        arr[i] = buf.view(np.int32)
    out = _get(_put(arr))
    return out.view(np.uint8).tobytes()[:n]


@metrics.spanned("kernel.put")
def _put(arr: np.ndarray) -> jax.Array:
    """The copy to the device and the kernel's dispatch."""
    return xor_parity(jax.numpy.asarray(arr))


@metrics.spanned("kernel.get")
def _get(dev: jax.Array) -> np.ndarray:
    """The wait for the kernel and the copy back."""
    return np.asarray(dev)


def reconstruct_bytes(survivors: list[bytes], parity: bytes,
                      length: int) -> bytes:
    return parity_bytes(survivors + [parity])[:length]
