"""Production mesh construction.

Called as a FUNCTION so importing this module never touches jax device
state. Single pod = 256 chips (16, 16) ("data", "model"); multi-pod adds a
leading "pod" axis (outer data parallelism whose gradient all-reduce crosses
pods on DCN).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axes. jax 0.9 defaults to Explicit axes,
    under which the model's `with_sharding_constraint` calls raise."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, devices=None):
    """("data", "model") mesh over `devices` (default: all of them)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    return make_mesh((n // model, model), ("data", "model"), devices=devices)


# TPU v5e hardware constants (per chip) — roofline denominators.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW = 50e9                 # bytes/s per link (~ per-direction)
HBM_BYTES = 16e9              # capacity
