"""Public jit'd wrappers for the Pallas kernels.

On the CPU backend the kernels run in interpret mode (the kernel body is
executed op-by-op for correctness); on TPU they compile for real. Any
other backend is an error. Callers use these wrappers and never touch
`interpret` directly.
"""
from __future__ import annotations

import jax
import numpy as np

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


def xor_parity(blocks, *, block=4096):
    block = min(block, blocks.shape[1])
    return _par.xor_parity(blocks, block=block, interpret=_interpret())


def reconstruct(survivors, parity, *, block=4096):
    block = min(block, parity.shape[0])
    return _par.reconstruct(survivors, parity, block=block,
                            interpret=_interpret())


# ------------------------------------------------------- byte helpers
def parity_bytes(chunks: list[bytes]) -> bytes:
    """XOR parity over equal-length byte chunks (pads the tail)."""
    n = max(len(c) for c in chunks)
    n4 = -(-n // 4) * 4
    arr = np.zeros((len(chunks), n4 // 4), np.int32)
    for i, c in enumerate(chunks):
        buf = np.zeros(n4, np.uint8)
        buf[:len(c)] = np.frombuffer(c, np.uint8)
        arr[i] = buf.view(np.int32)
    out = np.asarray(xor_parity(jax.numpy.asarray(arr)))
    return out.view(np.uint8).tobytes()[:n]


def reconstruct_bytes(survivors: list[bytes], parity: bytes,
                      length: int) -> bytes:
    return parity_bytes(survivors + [parity])[:length]
