"""Mellum2 (MellumForCausalLM), its loss and gradients, in plain jax.numpy
at float32, every matmul at the highest precision, on one device's share
of the experts.

Follows the published config.json: token embedding; per layer a pre-norm
grouped-query attention (no query or key norm; the config declares none),
rotary position embedding over the two halves of each head chosen by the
layer's type, causal softmax attention, then a pre-norm sparse MLP, each
with a residual; a final RMSNorm and the untied head. `sliding_attention`
layers see the last `sliding_window` keys and use the default RoPE;
`full_attention` layers see every earlier key and use YaRN, whose
inverse frequencies follow HF's `_compute_yarn_parameters` (with its
default `truncate`) and whose cos and sin are scaled by
`attention_factor`. The MLP's router is a softmax over all published
experts; each token takes its top `num_experts_per_tok` and renormalizes
their weights (`norm_topk_prob`).

The share: only the held experts (`expert_offset` and the next
`num_experts` of them) are computed, densely, every held expert on every
token, and weighted by a combine matrix that is zero where a token did
not route to it; the absent experts' part is left out, as the program
leaves it out. The loss is the mean cross entropy over every position
plus the trainer's auxiliary term: 0.01 times the Switch balance term
(experts times the sum over experts of mean router probability times the
share of tokens whose first choice it is), summed over the layers.

Departures from a whole model: attention is computed a block of queries
at a time, and the cross entropy a block of positions at a time, so that
the float32 scores of 4096-token sequences fit on one chip; neither
changes the mathematics.

The weights are the benchmark's tree (see `drivers/train.py`): matrices
act as `x @ w`, layers are stacked on a leading axis, and each RMSNorm
stores its gain as an offset from 1 (gain = 1 + w).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
AUX_WEIGHT = 0.01


@dataclasses.dataclass(frozen=True)
class Spec:
    """What the reference reads of a configuration, hashable."""
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    layer_types: tuple
    window: int
    theta_sliding: float
    theta_full: float
    yarn: tuple          # factor, original max positions, beta_fast,
                         # beta_slow, attention_factor
    router_experts: int
    held: int
    offset: int
    top_k: int


def spec(cfg: dict) -> Spec:
    n = cfg["num_hidden_layers"]
    rp = cfg["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default":
        raise ValueError("the reference knows YaRN on full layers and the "
                         "default RoPE on sliding ones")
    return Spec(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        eps=cfg["rms_norm_eps"], layer_types=tuple(cfg["layer_types"][:n]),
        window=cfg["sliding_window"],
        theta_sliding=float(sliding["rope_theta"]),
        theta_full=float(full["rope_theta"]),
        yarn=(float(full["factor"]),
              int(full["original_max_position_embeddings"]),
              float(full["beta_fast"]), float(full["beta_slow"]),
              float(full["attention_factor"])),
        router_experts=cfg["num_experts_published"],
        held=cfg["num_experts"], offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"])


def mm(spec_: str, a, b):
    return jnp.einsum(spec_, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def default_inv_freq(dim: int, base: float) -> np.ndarray:
    return 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """HF `_compute_yarn_parameters`' inverse frequencies."""
    extrapolation = default_inv_freq(dim, base)
    interpolation = extrapolation / factor

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1 - ramp
    return interpolation * (1 - keep) + extrapolation * keep


def rope(x, inv_freq, scale: float):
    """x: (B, S, H, D); positions 0..S-1; cos and sin times `scale`."""
    S, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv_freq, jnp.float32)[None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int, block: int):
    """Causal softmax attention, a block of `block` queries at a time;
    `window` > 0 keeps the last `window` keys of each query."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    nb = S // block
    qb = q.reshape(B, nb, block, Hkv, H // Hkv, D)

    @jax.checkpoint
    def one(c):
        s = mm("bqhgd,bkhd->bhgqk", qb[:, c], k) / jnp.sqrt(jnp.float32(D))
        qpos = c * block + jnp.arange(block)[:, None]
        kpos = jnp.arange(S)[None, :]
        seen = kpos <= qpos
        if window:
            seen = seen & (qpos - kpos < window)
        s = jnp.where(seen, s, -jnp.inf)
        return mm("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, jnp.arange(nb))        # (nb, B, block, Hkv, G, D)
    return out.swapaxes(0, 1).reshape(B, S, H * D)


def sparse_mlp(sp: Spec, h, p):
    """The held experts' part of the sparse MLP, and the balance term."""
    B, S, d = h.shape
    probs = jax.nn.softmax(mm("bsd,de->bse", h, p["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, sp.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # combine[b, s, e]: the weight of held expert e for the token, 0 where
    # the token did not choose it
    combine = jnp.sum(jax.nn.one_hot(top_e - sp.offset, sp.held)
                      * top_p[..., None], axis=-2)
    g = mm("bsd,edf->bsef", h, p["wg"])
    u = mm("bsd,edf->bsef", h, p["wu"])
    a = jax.nn.silu(g) * u * combine[..., None]
    y = mm("bsef,efd->bsd", a, p["wd"])
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(top_e[..., 0], sp.router_experts),
                  axis=(0, 1))
    return y, sp.router_experts * jnp.sum(me * ce)


def attend(sp: Spec, kind: str, x, p, block: int):
    """The attention half of a layer, with its residual."""
    B, S, d = x.shape
    H, Hkv, D = sp.heads, sp.kv_heads, sp.head_dim
    h = rms_norm(x, p["ln"], sp.eps)
    q = mm("bsd,de->bse", h, p["wq"]).reshape(B, S, H, D)
    k = mm("bsd,de->bse", h, p["wk"]).reshape(B, S, Hkv, D)
    v = mm("bsd,de->bse", h, p["wv"]).reshape(B, S, Hkv, D)
    if kind == "full_attention":
        factor, original, fast, slow, scale = sp.yarn
        inv = yarn_inv_freq(D, sp.theta_full, factor, original, fast, slow)
        window = 0
    elif kind == "sliding_attention":
        inv, scale, window = default_inv_freq(D, sp.theta_sliding), 1.0, \
            sp.window
    else:
        raise ValueError(f"no reference for layer type {kind!r}")
    o = attention(rope(q, inv, scale), rope(k, inv, scale), v, window,
                  min(block, S))
    return x + mm("bse,ed->bsd", o, p["wo"])


def layer(sp: Spec, kind: str, x, p, block: int):
    x = attend(sp, kind, x, p["attn"], block)
    y, aux = sparse_mlp(sp, rms_norm(x, p["mlp"]["ln"], sp.eps), p["mlp"])
    return x + y, aux


def loss(sp: Spec, params, tokens, labels, block: int = 512,
         ce_chunks: int = 4):
    x = params["embed"][tokens]
    aux = 0.0
    for i, kind in enumerate(sp.layer_types):
        p = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        x, a = jax.checkpoint(functools.partial(
            layer, sp, kind, block=block))(x, p)
        aux = aux + a
    x = rms_norm(x, params["final_ln"], sp.eps)
    B, S, d = x.shape
    xs = x.reshape(B, ce_chunks, S // ce_chunks, d).swapaxes(0, 1)
    ls = labels.reshape(B, ce_chunks, S // ce_chunks).swapaxes(0, 1)

    @jax.checkpoint
    def chunk(acc, xl):
        xc, lc = xl
        logits = mm("bsd,dv->bsv", xc, params["lm_head"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - ll), None

    tot, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (xs, ls))
    return tot / (B * S) + AUX_WEIGHT * aux


def next_token_labels(tokens):
    """Each position predicts the next token of its stored sequence; the
    last position predicts token 0 (the task as the configuration states
    it)."""
    return jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)


@functools.partial(jax.jit, static_argnums=(0,))
def loss_and_grads(sp: Spec, params, tokens):
    labels = next_token_labels(tokens)
    val, grads = jax.value_and_grad(
        functools.partial(loss, sp))(params, tokens, labels)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return val, grads, gnorm


@functools.partial(jax.jit, static_argnums=(0,))
def selections(sp: Spec, params, tokens):
    """Each layer's expert choices, (layers, B, S, top_k) in ascending
    order: the routing the reference takes, to count where another
    computation of the model routes otherwise."""
    x = params["embed"][tokens]
    out = []
    for i, kind in enumerate(sp.layer_types):
        p = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        x = attend(sp, kind, x, p["attn"], 512)
        h = rms_norm(x, p["mlp"]["ln"], sp.eps)
        _, top_e = jax.lax.top_k(mm("bsd,de->bse", h, p["mlp"]["router"]),
                                 sp.top_k)
        out.append(jnp.sort(top_e, axis=-1))
        x = x + sparse_mlp(sp, h, p["mlp"])[0]
    return jnp.stack(out)
