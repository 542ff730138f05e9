"""The seed a run's weights and state are drawn from. What depends on a
configuration's architecture is in `archs/<architecture>.py`, found by
`registry.arch`."""
from __future__ import annotations

import numpy as np


def seed32(seed: int) -> int:
    """A seed JAX's PRNG takes, drawn from a run's seed of any size."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))
