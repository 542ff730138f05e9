"""Shared layers + parameter-definition infrastructure.

Every model builds a pytree of ParamDef (shape, logical spec, init); from it we
derive (a) real initialized arrays for CPU smoke tests, (b) ShapeDtypeStructs +
NamedShardings for the multi-pod dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel import shardings as sh


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    logical: tuple  # logical axis per dim: "model" | "batch" | None
    init: str = "normal"  # normal | zeros | ones | embed
    scale: float = 1.0

    def struct(self, dtype) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, dtype)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_structs(defs, dtype):
    return jax.tree.map(lambda d: d.struct(dtype), defs, is_leaf=is_def)


def tree_specs(defs, mesh, fsdp: bool = False):
    """Parameter NamedShardings. With fsdp=True, each tensor additionally
    shards its largest still-replicated dim over the "data" axis (ZeRO-3
    within a pod; replicated across pods — DCN all-gathers would dominate).
    GSPMD then all-gathers per layer inside the scan and reduce-scatters
    gradients."""
    if not fsdp or "data" not in getattr(mesh, "axis_names", ()):
        return jax.tree.map(
            lambda d: sh.named(mesh, d.logical, d.shape), defs,
            is_leaf=is_def)
    dsize = mesh.shape["data"]

    def spec(d: ParamDef):
        base = list(sh.resolve_spec(mesh, d.logical, d.shape))
        cands = [(dim, i) for i, (dim, s) in enumerate(zip(d.shape, base))
                 if s is None and dim % dsize == 0 and dim >= dsize]
        if cands:
            _, i = max(cands)
            base[i] = "data"
        return jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*base))

    return jax.tree.map(spec, defs, is_leaf=is_def)


def tree_init(defs, key, dtype=jnp.float32):
    """Initialize real arrays (tiny smoke configs only)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, d in zip(keys, leaves):
        if d.init == "zeros":
            a = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            a = jnp.ones(d.shape, dtype)
        else:
            fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
            std = d.scale / np.sqrt(max(1, fan_in))
            a = (jax.random.normal(k, d.shape, jnp.float32) * std).astype(dtype)
        out.append(a)
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------- layers

def rms_norm(x, w, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope(x, positions, theta: float):
    """x: (..., S, H, D) rotary over D; positions (..., S)."""
    half = x.shape[-1] // 2
    freqs = jnp.arange(0, half, dtype=jnp.float32)
    return rope_table(x, positions, theta ** (-freqs / half))


def rope_inv_freq(head_dim: int, theta: float) -> np.ndarray:
    """Default RoPE: one inverse frequency per pair of dims, (head_dim/2,)."""
    half = head_dim // 2
    return theta ** (-np.arange(half, dtype=np.float64) / half)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_pos: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN (arXiv:2309.00071), as HF's `_compute_yarn_parameters` with
    `truncate`: frequencies that turn more than `beta_fast` times over the
    original context keep their value, those that turn less than
    `beta_slow` times are divided by `factor`, and a linear ramp over the
    pair index blends the two in between."""
    half = head_dim // 2
    extra = rope_inv_freq(head_dim, theta)
    inter = extra / factor

    def pair_index(rotations):
        return head_dim * np.log(original_max_pos / (
            rotations * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(pair_index(beta_fast)), 0)
    high = min(np.ceil(pair_index(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def rope_table(x, positions, inv, scale=None):
    """`rope` with the inverse frequencies given, (D/2,) float32, and cos
    and sin multiplied by `scale` where given (YaRN's attention factor)."""
    half = x.shape[-1] // 2
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if scale is not None:
        sin, cos = sin * scale, cos * scale
    sin = sin[..., None, :]  # broadcast over heads
    cos = cos[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
            "relu": jax.nn.relu}[name]


def attention_scores(q, k, v, mask, dtype=jnp.bfloat16):
    """Reference (non-flash) attention. q:(B,Sq,H,D) k/v:(B,Sk,Hkv,D).

    GQA handled by reshaping q into (B,Sq,Hkv,G,D)."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits / np.sqrt(D)
    logits = jnp.where(mask[:, None, None, :, :] if mask.ndim == 3 else mask,
                       logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, H, D)


def causal_mask(Sq, Sk, window=0, prefix_len=0, q_offset=0):
    """(Sq, Sk) boolean mask. window>0 = sliding window; prefix bidirectional."""
    qp = jnp.arange(Sq)[:, None] + q_offset
    kp = jnp.arange(Sk)[None, :]
    m = kp <= qp
    if isinstance(window, (int, np.integer)):
        if window > 0:
            m = m & (qp - kp < window)
    else:  # traced scalar (per-layer, inside scan)
        m = m & jnp.where(window > 0, qp - kp < jnp.maximum(window, 1), True)
    if prefix_len:
        both_prefix = (qp < prefix_len) & (kp < prefix_len)
        m = m | both_prefix
    return m


def decode_mask(Smax, pos, window=0):
    """(1, Smax) mask for one-token decode at position `pos` (inclusive)."""
    kp = jnp.arange(Smax)[None, :]
    m = kp <= pos
    if isinstance(window, (int, np.integer)):
        if window > 0:
            m = m & (pos - kp < window)
    else:
        m = m & jnp.where(window > 0, pos - kp < jnp.maximum(window, 1), True)
    return m
