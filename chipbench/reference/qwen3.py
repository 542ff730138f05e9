"""Qwen3 decoder-only language model, its loss and gradients, in plain
jax.numpy at float32, every matmul at the highest precision.

Follows the published architecture (Qwen3ForCausalLM): token embedding;
per layer a pre-norm grouped-query attention with RMSNorm on each query
and key head, rotary position embedding over the two halves of each head,
causal softmax attention, then a pre-norm SwiGLU MLP, each with a
residual; a final RMSNorm and the tied head (the embedding transposed).
The loss is the mean cross entropy over every position.

The weights are the benchmark's tree (see `drivers/train.py`): matrices
act as `x @ w`, layers are stacked on a leading axis, and each RMSNorm
stores its gain as an offset from 1 (gain = 1 + w).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul_f32(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def rope(x, theta: float):
    """x: (B, S, H, D); positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(cfg: dict, mm, x, p):
    B, S, d = x.shape
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    a = p["attn"]
    h = rms_norm(x, a["ln"], eps)
    q = mm("bsd,de->bse", h, a["wq"]).reshape(B, S, H, D)
    k = mm("bsd,de->bse", h, a["wk"]).reshape(B, S, Hkv, D)
    v = mm("bsd,de->bse", h, a["wv"]).reshape(B, S, Hkv, D)
    q = rope(rms_norm(q, a["qn"], eps), float(cfg["rope_theta"]))
    k = rope(rms_norm(k, a["kn"], eps), float(cfg["rope_theta"]))
    q = q.reshape(B, S, Hkv, H // Hkv, D)
    s = mm("bqhgd,bkhd->bhgqk", q, k) / jnp.sqrt(jnp.float32(D))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)
    x = x + mm("bse,ed->bsd", o.reshape(B, S, H * D), a["wo"])
    m = p["mlp"]
    h = rms_norm(x, m["ln"], eps)
    g = jax.nn.silu(mm("bsd,df->bsf", h, m["wg"]))
    u = mm("bsd,df->bsf", h, m["wu"])
    return x + mm("bsf,fd->bsd", g * u, m["wd"])


def loss(cfg: dict, mm, params, tokens, labels, ce_chunks: int = 4):
    x = params["embed"][tokens]

    def body(x, p):
        return jax.checkpoint(functools.partial(layer, cfg, mm))(x, p), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_ln"], cfg["rms_norm_eps"])
    B, S, d = x.shape
    xs = x.reshape(B, ce_chunks, S // ce_chunks, d).swapaxes(0, 1)
    ls = labels.reshape(B, ce_chunks, S // ce_chunks).swapaxes(0, 1)

    @jax.checkpoint
    def chunk(acc, xl):
        xc, lc = xl
        logits = mm("bsd,vd->bsv", xc, params["embed"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - ll), None

    tot, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (xs, ls))
    return tot / (B * S)


def next_token_labels(tokens):
    """Each position predicts the next token of its stored sequence; the
    last position predicts token 0 (the task as the configuration states
    it)."""
    return jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)


@functools.partial(jax.jit, static_argnums=(0,))
def loss_and_grads(cfg_items: tuple, params, tokens):
    cfg = dict(cfg_items)
    labels = next_token_labels(tokens)
    val, grads = jax.value_and_grad(
        functools.partial(loss, cfg, matmul_f32))(params, tokens, labels)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return val, grads, gnorm

