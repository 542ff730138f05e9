"""Logical→physical sharding resolution for the production mesh.

Logical axis names used by model code:
  "batch"  -> data-parallel axes ("pod","data") when present
  "model"  -> tensor/expert-parallel axis ("model",)
  None     -> replicated

Resolution is divisibility-aware: a dim is only sharded if the mesh axis
product divides it (GSPMD can pad, but we keep in/out shardings exact).
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def resolve_spec(mesh: Mesh, logical: Sequence, shape: Sequence[int]) -> P:
    """Map a logical spec (tuple of "batch"/"model"/"model2"/None per dim) to
    a PartitionSpec, dropping entries whose mesh size does not divide the dim.

    "model2" is a *fallback* model-axis slot: it shards over "model" only if
    no earlier dim claimed the model axis (used e.g. to shard KV-cache
    head_dim when n_kv_heads is not divisible by the model axis)."""
    out = []
    model_used = False
    batch_used = False
    deferred_batch2 = []
    for i, (dim, name) in enumerate(zip(shape, logical)):
        if name is None:
            out.append(None)
            continue
        if name in ("model", "model2"):
            # the model axis can be claimed by at most one dim
            if model_used:
                out.append(None)
                continue
            name = "model"
        if name == "batch2":
            # fallback slot: takes the dp axes only if no "batch" dim
            # could (e.g. decode KV caches with batch=1: the SEQUENCE dim
            # shards over "data" instead)
            deferred_batch2.append((i, dim))
            out.append(None)
            continue
        axes = dp_axes(mesh) if name == "batch" else ("model",)
        axes = tuple(a for a in axes if a in mesh.axis_names)
        if axes and dim % _axis_size(mesh, axes) == 0:
            if name == "model":
                model_used = True
            if name == "batch":
                batch_used = True
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    if deferred_batch2 and not batch_used:
        axes = dp_axes(mesh)
        for i, dim in deferred_batch2:
            if axes and dim % _axis_size(mesh, axes) == 0:
                out[i] = axes if len(axes) > 1 else axes[0]
                break
    return P(*out)


def named(mesh: Mesh, logical: Sequence, shape: Sequence[int]) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(mesh, logical, shape))


def constrain(x: jax.Array, logical: Sequence) -> jax.Array:
    """with_sharding_constraint against the ambient mesh, divisibility-aware.

    Safe to call outside jit/mesh context (returns x unchanged)."""
    mesh = _MESH[0]
    if mesh is None or mesh.empty:
        return x
    spec = resolve_spec(mesh, logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# The mesh model-internal constraints resolve against. Step builders set it
# only while their step function is traced (`ambient_mesh`), so a later
# trace that has no mesh (e.g. BatchedServer) never sees a stale one.
_MESH: list[Mesh | None] = [None]


@contextlib.contextmanager
def ambient_mesh(mesh: Mesh | None):
    prev, _MESH[0] = _MESH[0], mesh
    try:
        yield mesh
    finally:
        _MESH[0] = prev


def get_ambient_mesh() -> Mesh | None:
    return _MESH[0]
