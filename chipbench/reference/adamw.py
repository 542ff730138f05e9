"""AdamW, one leaf at a time, in plain jax.numpy at float32: the update
the configuration's optimizer states, for any architecture's reference."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def adamw_leaf(p, g, m, v, *, lr, scale, bc1, bc2, b1, b2, eps, wd):
    """One AdamW step of one leaf, its gradient scaled by `scale` (the
    global-norm clip)."""
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    return p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p), m, v
