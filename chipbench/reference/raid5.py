"""RAID-5 over K data units and one rotating parity unit, in numpy.

A file is cut into rounds of K units of `stripe_size` bytes. Round r's
parity unit is the XOR of its K data units. With left-symmetric rotation
over n = K + 1 objects, round r's parity lies on object (n - 1 - r) mod n
and data unit i on object i, or i + 1 from the parity's object on. Every
object holds its units of rounds 0, 1, 2, ... back to back.
"""
from __future__ import annotations

import numpy as np


def parity_object(r: np.ndarray, n: int) -> np.ndarray:
    return (n - 1 - r % n) % n


def expected_objects(file_bytes: np.ndarray, ssz: int, k: int):
    """(objects, is_parity): the n objects' bytes as (n, rounds, ssz), and
    which units are parity, as (n, rounds). The file is whole rounds."""
    if file_bytes.size % (ssz * k):
        raise ValueError("the file is not a whole number of rounds")
    units = file_bytes.reshape(-1, k, ssz)
    rounds = units.shape[0]
    parity = np.bitwise_xor.reduce(units, axis=1)
    n = k + 1
    objs = np.empty((n, rounds, ssz), np.uint8)
    is_parity = np.zeros((n, rounds), bool)
    r = np.arange(rounds)
    p = parity_object(r, n)
    objs[p, r] = parity
    is_parity[p, r] = True
    for i in range(k):
        s = np.where(i < p, i, i + 1)
        objs[s, r] = units[:, i]
    return objs, is_parity


def compare_objects(file_bytes: np.ndarray, objects: list, ssz: int, k: int,
                    rotation: str = "left-symmetric") -> tuple[int, int]:
    """Bytes that differ from the reference, in data units and in parity
    units, over the n objects as stored. A byte missing or extra counts as
    one that differs."""
    if rotation != "left-symmetric":
        raise ValueError(f"no reference for rotation {rotation!r}")
    want, is_parity = expected_objects(file_bytes, ssz, k)
    n, rounds, _ = want.shape
    if len(objects) != n:
        raise ValueError(f"{len(objects)} objects, the layout has {n}")
    data_wrong = parity_wrong = 0
    for s in range(n):
        got = np.asarray(objects[s], np.uint8)
        w = want[s].reshape(-1)
        m = min(len(got), len(w))
        # a byte missing from a short object counts as one that differs
        diff = np.ones(len(w), bool)
        diff[:m] = got[:m] != w[:m]
        per_unit = diff.reshape(rounds, ssz).sum(axis=1)
        parity_wrong += int(per_unit[is_parity[s]].sum())
        data_wrong += int(per_unit[~is_parity[s]].sum())
        data_wrong += max(0, len(got) - len(w))
    return data_wrong, parity_wrong
