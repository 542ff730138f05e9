"""RAID-0 striping and column parity, in numpy.

A file striped over K objects in units of `stripe_size` bytes puts unit u
on object u mod K, at offset (u div K) * stripe_size. Its column parity is
the XOR of the K objects, each zero-padded to the first (longest) one.
"""
from __future__ import annotations

import numpy as np


def objects(data: np.ndarray, ssz: int, k: int) -> list[np.ndarray]:
    """The K objects' bytes for a file of bytes `data` (uint8)."""
    n = data.size
    rnd = ssz * k
    padded = np.zeros(-(-n // rnd) * rnd, np.uint8)
    padded[:n] = data
    cols = padded.reshape(-1, k, ssz).transpose(1, 0, 2).reshape(k, -1)
    out = []
    for i in range(k):
        units = range(i, -(-n // ssz), k)
        length = sum(min(ssz, n - u * ssz) for u in units)
        out.append(cols[i, :length])
    return out


def column_parity(data: np.ndarray, ssz: int, k: int) -> np.ndarray:
    objs = objects(data, ssz, k)
    objs = [o for o in objs if o.size]
    if not objs:
        return np.zeros(0, np.uint8)
    out = np.zeros(objs[0].size, np.uint8)
    for o in objs:
        out[:o.size] ^= o
    return out


def count_wrong(got: np.ndarray, want: np.ndarray) -> int:
    """Bytes that differ, a byte missing or extra counting as one."""
    m = min(got.size, want.size)
    return int(np.count_nonzero(got[:m] != want[:m])) + abs(
        got.size - want.size)
