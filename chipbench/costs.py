"""Bytes the kernels need, from shapes and counts. A model's FLOPs are
in its architecture's file, `archs/<architecture>.py`.

The XOR parity kernel reads K rows of N int32 words and writes one row:
(K + 1) * N * 4 bytes, no arithmetic worth counting, so it is bound by
memory bandwidth. Bytes of padding the kernel wrapper adds are not work
the algorithm needs and are not counted.
"""
from __future__ import annotations


def xor_kernel_bytes(rows: int, row_bytes: int) -> int:
    """Bytes one XOR of `rows` rows of `row_bytes` bytes moves: the rows
    in and the parity out, each row rounded up to whole int32 words."""
    words = -(-row_bytes // 4)
    return (rows + 1) * words * 4


def raid5_kernel_bytes(parity_bytes: int, k: int) -> int:
    """Kernel bytes behind `parity_bytes` of raid5 parity or of
    reconstructed units (`lov.parity_bytes`, `lov.reconstruct_bytes`)
    over a K-data-unit stripe: a full round XORs K rows into one (write),
    and a lost unit is K - 1 surviving data rows plus the parity (read)."""
    return (k + 1) * parity_bytes


def ckpt_parity_kernel_bytes(nbytes: int, stripe_size: int,
                             stripe_count: int, piece_bytes: int) -> int:
    """Kernel bytes of one checkpoint leaf's parity: the leaf's stripe
    columns are XORed a run of whole stripe rounds at a time (at most
    `piece_bytes`), each run's non-empty columns padded to its longest."""
    if nbytes <= 0:
        return 0
    if stripe_count < 2:
        return xor_kernel_bytes(1, nbytes)
    rnd = stripe_size * stripe_count
    step = max(1, piece_bytes // rnd) * rnd
    total = 0
    for a in range(0, nbytes, step):
        n = min(step, nbytes - a)
        cols = [sum(min(stripe_size, n - u * stripe_size)
                    for u in range(i, -(-n // stripe_size), stripe_count))
                for i in range(stripe_count)]
        cols = [c for c in cols if c > 0]
        total += xor_kernel_bytes(len(cols), max(cols))
    return total

