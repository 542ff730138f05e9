"""Unified decoder/encoder-decoder transformer.

Covers: yi-9b, gemma3-12b (5:1 local:global), qwen3-4b (qk_norm), qwen2-7b
(qkv bias), paligemma-3b (patch-prefix VLM), phi3.5-moe & dbrx (MoE),
whisper-tiny (enc-dec, frame-stub encoder).

All layer stacks are lax.scan over stacked params; per-layer attention windows
are a scanned int32 array so local/global mixes share one traced body.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.config import ModelConfig
from repro.parallel.shardings import constrain


# ----------------------------------------------------------------- params

def _attn_defs(cfg: ModelConfig, n: int, cross: bool = False):
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    D = lambda *s, lg, init="normal": L.ParamDef((n, *s), (None, *lg), init)
    p = {
        "ln": D(d, lg=(None,), init="zeros"),
        "wq": D(d, H * Dh, lg=(None, "model")),
        "wk": D(d, Hkv * Dh, lg=(None, "model")),
        "wv": D(d, Hkv * Dh, lg=(None, "model")),
        "wo": D(H * Dh, d, lg=("model", None)),
    }
    if cfg.qkv_bias and not cross:
        p |= {"bq": D(H * Dh, lg=("model",), init="zeros"),
              "bk": D(Hkv * Dh, lg=("model",), init="zeros"),
              "bv": D(Hkv * Dh, lg=("model",), init="zeros")}
    if cfg.qk_norm and not cross:
        p |= {"qn": D(Dh, lg=(None,), init="zeros"),
              "kn": D(Dh, lg=(None,), init="zeros")}
    return p


def _mlp_defs(cfg: ModelConfig, n: int):
    d = cfg.d_model
    D = lambda *s, lg, init="normal": L.ParamDef((n, *s), (None, *lg), init)
    if cfg.is_moe:
        E, f = cfg.experts_here, cfg.d_ff_expert
        return {
            "ln": D(d, lg=(None,), init="zeros"),
            "router": D(d, cfg.n_experts, lg=(None, None)),
            "wg": D(E, d, f, lg=("model", None, None)),
            "wu": D(E, d, f, lg=("model", None, None)),
            "wd": D(E, f, d, lg=("model", None, None)),
        }
    f = cfg.d_ff
    return {
        "ln": D(d, lg=(None,), init="zeros"),
        "wg": D(d, f, lg=(None, "model")),
        "wu": D(d, f, lg=(None, "model")),
        "wd": D(f, d, lg=("model", None)),
    }


def param_defs(cfg: ModelConfig):
    d, V = cfg.d_model, cfg.vocab
    n = cfg.n_layers
    defs = {
        "embed": L.ParamDef((V, d), ("model", None), scale=float(np.sqrt(d))),
        "final_ln": L.ParamDef((d,), (None,), init="zeros"),
        "layers": {"attn": _attn_defs(cfg, n), "mlp": _mlp_defs(cfg, n)},
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = L.ParamDef((d, V), (None, "model"))
    if cfg.enc_layers:  # whisper-style encoder + cross attention
        ne = cfg.enc_layers
        defs["enc_layers"] = {"attn": _attn_defs(cfg, ne),
                              "mlp": _mlp_defs(cfg, ne)}
        defs["enc_final_ln"] = L.ParamDef((d,), (None,), init="zeros")
        defs["layers"]["xattn"] = _attn_defs(cfg, n, cross=True)
        defs["dec_pos"] = L.ParamDef((32768, d), (None, None), init="zeros")
    if cfg.n_patches:  # paligemma: projection for stub patch embeddings
        defs["patch_proj"] = L.ParamDef((d, d), (None, "model"))
    return defs


def windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer attention window (0 = global/full)."""
    w = np.zeros(cfg.n_layers, np.int32)
    if cfg.sliding_window and cfg.global_every:
        for i in range(cfg.n_layers):
            if (i + 1) % cfg.global_every != 0:
                w[i] = cfg.sliding_window
    elif cfg.sliding_window:
        w[:] = cfg.sliding_window
    return w


def rope_tables(cfg: ModelConfig):
    """Per-layer RoPE where it differs by layer type: (inverse frequencies
    (n_layers, head_dim/2), cos/sin scale (n_layers,)), float32, with YaRN
    on the full-attention layers and the default on windowed ones. None
    where every layer uses the default RoPE."""
    if not cfg.yarn_factor:
        return None
    base = L.rope_inv_freq(cfg.head_dim, cfg.rope_theta)
    yarn = L.yarn_inv_freq(cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
                           cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                           cfg.yarn_beta_slow)
    full = windows(cfg) == 0
    inv = np.where(full[:, None], yarn[None], base[None])
    return (inv.astype(np.float32),
            np.where(full, cfg.yarn_attention_factor, 1.0).astype(np.float32))


def _rope(cfg, x, positions, rope):
    if rope is not None:
        return L.rope_table(x, positions, *rope)
    return L.rope(x, positions, cfg.rope_theta)


# ----------------------------------------------------------------- blocks

def _qkv(cfg, p, x, cdt):
    B, S, d = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    q = h @ p["wq"].astype(cdt)
    k = h @ p["wk"].astype(cdt)
    v = h @ p["wv"].astype(cdt)
    if "bq" in p:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if "qn" in p:
        q = L.rms_norm(q, p["qn"], cfg.norm_eps)
        k = L.rms_norm(k, p["kn"], cfg.norm_eps)
    q = constrain(q, ("batch", None, "model", None))
    return q, k, v


def _attn_out(cfg, p, out, x, cdt):
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    out = out @ p["wo"].astype(cdt)
    return constrain(x + out, ("batch", None, None))


def _chunked_attention(q, k, v, window, prefix_len, chunk, cdt,
                       q_offset_base=0):
    """Row-chunked softmax attention: bounds logits memory to
    B*H*chunk*Sk. Used for the 32k prefill cells."""
    B, Sq, H, Dh = q.shape
    nchunk = Sq // chunk
    qs = q.reshape(B, nchunk, chunk, H, Dh).transpose(1, 0, 2, 3, 4)

    def body(_, qc_i):
        qc, i = qc_i
        mask = L.causal_mask(chunk, k.shape[1], window, prefix_len,
                             q_offset=q_offset_base + i * chunk)
        oc = L.attention_scores(qc, k, v, mask[None], dtype=cdt)
        return None, oc

    _, out = jax.lax.scan(body, None, (qs, jnp.arange(nchunk)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, Dh)


def attn_block(cfg, p, x, window, prefix_len, rc, positions=None,
               rope=None):
    """Full-sequence self attention (train / prefill). Returns (x, (k, v)).
    `rope`: this layer's row of `rope_tables`, where layers differ."""
    cdt = jnp.dtype(rc.compute_dtype)
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, p, x, cdt)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if cfg.rope_theta:
        q = _rope(cfg, q, positions, rope)
        k = _rope(cfg, k, positions, rope)
    k = constrain(k, ("batch", None, "model", None))
    v = constrain(v, ("batch", None, "model", None))
    if rc.attn_impl == "flash" and not prefix_len \
            and isinstance(window, (int, np.integer)):
        # Pallas TPU kernel (kernels/flash_attention.py); prefix
        # (bidirectional) attention and per-layer traced windows fall back
        # to the chunked path below.
        from repro.kernels import ops as kops
        out = kops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=int(window))
        out = out.transpose(0, 2, 1, 3).astype(cdt)
    elif rc.attn_impl == "chunked" or (rc.attn_impl == "auto" and S > 2048):
        chunk = next((c for c in (rc.attn_chunk, 512, 256, 128, 64)
                      if c <= S and S % c == 0), S)
        out = _chunked_attention(q, k, v, window, prefix_len, chunk, cdt)
    else:
        mask = L.causal_mask(S, S, window, prefix_len)
        out = L.attention_scores(q, k, v, mask[None], dtype=cdt)
    return _attn_out(cfg, p, out, x, cdt), (k, v)


def cross_attn_block(cfg, p, x, enc_kv, rc):
    cdt = jnp.dtype(rc.compute_dtype)
    B, S, d = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    q = (h @ p["wq"].astype(cdt)).reshape(B, S, H, Dh)
    k, v = enc_kv  # (B, F, Hkv, Dh) precomputed from encoder output
    mask = jnp.ones((1, S, k.shape[1]), bool)
    out = L.attention_scores(q, k, v, mask, dtype=cdt)
    return _attn_out(cfg, p, out, x, cdt)


def decode_attn_block(cfg, p, x, window, cache_k, cache_v, pos, rc,
                      rope=None):
    """One-token decode. cache_[kv]: (B, Smax, Hkv, Dh). Returns updated."""
    cdt = jnp.dtype(rc.compute_dtype)
    B = x.shape[0]
    q, k, v = _qkv(cfg, p, x, cdt)  # S == 1
    posv = jnp.full((B, 1), pos)
    if cfg.rope_theta:
        q = _rope(cfg, q, posv, rope)
        k = _rope(cfg, k, posv, rope)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, 1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, 1)
    mask = L.decode_mask(cache_k.shape[1], pos, window)
    out = L.attention_scores(q, cache_k, cache_v, mask[None], dtype=cdt)
    return _attn_out(cfg, p, out, x, cdt), (cache_k, cache_v)


def mlp_block(cfg, p, x, rc):
    cdt = jnp.dtype(rc.compute_dtype)
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    if cfg.is_moe:
        B, S, d = x.shape
        y, aux = moe_lib.moe_ffn(cfg, p, h.reshape(B * S, d), rc)
        return constrain(x + y.reshape(B, S, d), ("batch", None, None)), aux
    g = h @ p["wg"].astype(cdt)
    u = h @ p["wu"].astype(cdt)
    hidden = L.act_fn(cfg.act)(g) * u
    hidden = constrain(hidden, ("batch", None, "model"))
    y = hidden @ p["wd"].astype(cdt)
    return constrain(x + y, ("batch", None, None)), jnp.zeros((), jnp.float32)


# ----------------------------------------------------------------- stacks

def _maybe_remat(fn, rc):
    if rc.remat == "full":
        return jax.checkpoint(fn)
    return fn


def encoder_forward(cfg, params, frames, rc):
    """Whisper-style encoder over stub frame embeddings (B, F, d)."""
    x = frames.astype(jnp.dtype(rc.compute_dtype))

    def body(x, pl):
        x, _ = attn_block(cfg, pl["attn"], x, 0, x.shape[1], rc)
        x, _ = mlp_block(cfg, pl["mlp"], x, rc)
        return x, None

    x, _ = jax.lax.scan(_maybe_remat(body, rc), x, params["enc_layers"])
    return L.rms_norm(x, params["enc_final_ln"], cfg.norm_eps)


def _embed(cfg, params, tokens, rc):
    cdt = jnp.dtype(rc.compute_dtype)
    x = params["embed"].astype(cdt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cdt)
    return constrain(x, ("batch", None, None))


def _inputs_with_prefix(cfg, params, tokens, batch, rc):
    """Handle VLM patch prefix / whisper decoder positions."""
    x = _embed(cfg, params, tokens, rc)
    prefix_len = 0
    if cfg.n_patches:
        cdt = x.dtype
        patches = batch["patches"].astype(cdt) @ params["patch_proj"].astype(cdt)
        x = jnp.concatenate([patches, x], axis=1)
        prefix_len = cfg.n_patches
    if cfg.enc_layers:
        S = x.shape[1]
        x = x + params["dec_pos"].astype(x.dtype)[:S][None]
    return x, prefix_len


def forward(cfg: ModelConfig, params, batch, rc, return_cache=False):
    """Train/prefill forward. batch: tokens (B,S) [+ patches/frames].

    Returns (logits_source_x, prefix_len, cache, enc_kv, aux)."""
    tokens = batch["tokens"]
    x, prefix_len = _inputs_with_prefix(cfg, params, tokens, batch, rc)
    w_arr = windows(cfg)
    # uniform window -> keep it static (enables the flash kernel + avoids
    # a per-layer where() in the HLO); what differs by layer is scanned
    uniform = int(w_arr[0]) if (w_arr == w_arr[0]).all() else None
    per_layer = {}
    if uniform is None:
        per_layer["window"] = jnp.asarray(w_arr)
    tables = rope_tables(cfg)
    if tables is not None:
        per_layer["rope"] = tuple(jnp.asarray(t) for t in tables)
    enc_kv = None
    if cfg.enc_layers:
        enc_out = encoder_forward(cfg, params, batch["frames"], rc)
        # Pre-compute per-layer cross K/V (B,F,Hkv,Dh) inside the scan below.
        enc_kv = enc_out

    def body(x, sl):
        pl, lay = sl if per_layer else (sl, {})
        x, kv = attn_block(cfg, pl["attn"], x, lay.get("window", uniform),
                           prefix_len, rc, rope=lay.get("rope"))
        xkv = None
        if cfg.enc_layers:
            cdt = x.dtype
            B, F, d = enc_kv.shape
            Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
            xk = (enc_kv @ pl["xattn"]["wk"].astype(cdt)).reshape(B, F, Hkv, Dh)
            xv = (enc_kv @ pl["xattn"]["wv"].astype(cdt)).reshape(B, F, Hkv, Dh)
            x = cross_attn_block(cfg, pl["xattn"], x, (xk, xv), rc)
            xkv = (xk, xv)
        x, aux = mlp_block(cfg, pl["mlp"], x, rc)
        out = (kv, xkv) if return_cache else None
        return x, (out, aux)

    xs = (params["layers"], per_layer) if per_layer else params["layers"]
    x, (cache, aux) = jax.lax.scan(_maybe_remat(body, rc), x, xs)
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    if return_cache:
        (k, v), xkv = cache
        cache = {"k": k, "v": v}
        if cfg.enc_layers:
            cache["xk"], cache["xv"] = xkv
    return x, prefix_len, cache, enc_kv, jax.tree.map(jnp.sum, aux)


def unembed(cfg, params, x, rc):
    cdt = jnp.dtype(rc.compute_dtype)
    head = (params["embed"].astype(cdt).T if cfg.tie_embeddings
            else params["lm_head"].astype(cdt))
    logits = x @ head
    return constrain(logits, ("batch", None, "model"))


def init_cache(cfg: ModelConfig, batch_size: int, seq_len: int, dtype,
               windowed: bool = False):
    """KV-cache ShapeDtypeStruct-compatible zero pytree spec (shapes only).

    windowed=True (gemma3-style local:global mixes): local-attention
    layers keep a `sliding_window`-slot ring buffer instead of the full
    context — 6x less cache for a 5:1 mix (EXPERIMENTS.md §Perf gemma3)."""
    n, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    if windowed and cfg.sliding_window and cfg.global_every \
            and n % cfg.global_every == 0:
        ng = n // cfg.global_every
        nloc = cfg.global_every - 1
        W = min(cfg.sliding_window, seq_len)
        return {
            "k_loc": ((ng, nloc, batch_size, W, Hkv, Dh), dtype),
            "v_loc": ((ng, nloc, batch_size, W, Hkv, Dh), dtype),
            "k_glob": ((ng, batch_size, seq_len, Hkv, Dh), dtype),
            "v_glob": ((ng, batch_size, seq_len, Hkv, Dh), dtype),
        }
    c = {"k": ((n, batch_size, seq_len, Hkv, Dh), dtype),
         "v": ((n, batch_size, seq_len, Hkv, Dh), dtype)}
    if cfg.enc_layers:
        c["xk"] = ((n, batch_size, cfg.enc_frames, Hkv, Dh), dtype)
        c["xv"] = ((n, batch_size, cfg.enc_frames, Hkv, Dh), dtype)
    return c


def cache_logical():
    # seq dim falls back to the data axes ("batch2") when batch cannot
    # claim them (e.g. long_500k with global_batch=1)
    base = (None, "batch", "batch2", "model", "model2")
    return {"k": base, "v": base, "xk": base, "xv": base,
            "k_loc": (None, None, "batch", None, "model", "model2"),
            "v_loc": (None, None, "batch", None, "model", "model2"),
            "k_glob": base, "v_glob": base}


def decode_attn_block_ring(cfg, p, x, window, cache_k, cache_v, pos, rc):
    """Sliding-window decode against a RING buffer of `window` slots.
    Slot s holds absolute position pos - ((pos - s) mod window); the mask
    only rejects slots whose position is still negative (cold start)."""
    cdt = jnp.dtype(rc.compute_dtype)
    B = x.shape[0]
    W = cache_k.shape[1]
    q, k, v = _qkv(cfg, p, x, cdt)
    posv = jnp.full((B, 1), pos)
    if cfg.rope_theta:
        q = L.rope(q, posv, cfg.rope_theta)
        k = L.rope(k, posv, cfg.rope_theta)
    slot = jnp.mod(pos, W)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), slot, 1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), slot, 1)
    slots = jnp.arange(W)
    abs_pos = pos - jnp.mod(pos - slots, W)
    mask = (abs_pos >= 0)[None, :]
    out = L.attention_scores(q, cache_k, cache_v, mask[None], dtype=cdt)
    return _attn_out(cfg, p, out, x, cdt), (cache_k, cache_v)


def decode_windowed(cfg: ModelConfig, params, cache, token, pos, rc):
    """Decode for local:global mixes with ring-buffered local caches.
    Layers are scanned as (ng, global_every) groups: `global_every - 1`
    local layers then one global layer (gemma3's 5:1 pattern)."""
    x = _embed(cfg, params, token, rc)
    per = cfg.global_every
    ng = cfg.n_layers // per
    W = cfg.sliding_window
    grouped = jax.tree.map(
        lambda a: a.reshape(ng, per, *a.shape[1:]), params["layers"])
    tables = rope_tables(cfg)
    # the global layer's RoPE (YaRN where configured); local layers keep
    # the default
    glob_rope = None if tables is None else \
        (jnp.asarray(tables[0][per - 1]), jnp.asarray(tables[1][per - 1]))

    def loc_body(x, sl):
        pl, ck, cv = sl
        x, (ck, cv) = decode_attn_block_ring(cfg, pl["attn"], x, W, ck, cv,
                                             pos, rc)
        x, _ = mlp_block(cfg, pl["mlp"], x, rc)
        return x, (ck, cv)

    def group_body(x, sl):
        pg, ckl, cvl, ckg, cvg = sl
        loc = jax.tree.map(lambda a: a[: per - 1], pg)
        glob = jax.tree.map(lambda a: a[per - 1], pg)
        x, (ckl, cvl) = jax.lax.scan(loc_body, x, (loc, ckl, cvl))
        x, (ckg, cvg) = decode_attn_block(cfg, glob["attn"], x, 0, ckg,
                                          cvg, pos, rc, rope=glob_rope)
        x, _ = mlp_block(cfg, glob["mlp"], x, rc)
        return x, (ckl, cvl, ckg, cvg)

    x, (ckl, cvl, ckg, cvg) = jax.lax.scan(
        group_body, x, (grouped, cache["k_loc"], cache["v_loc"],
                        cache["k_glob"], cache["v_glob"]))
    new_cache = {"k_loc": ckl, "v_loc": cvl, "k_glob": ckg, "v_glob": cvg}
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed(cfg, params, x, rc)
    return logits, new_cache


def decode(cfg: ModelConfig, params, cache, token, pos, rc):
    """One-token decode step. token (B,1) int32; pos scalar int32.

    cache: {"k": (L,B,Smax,Hkv,Dh), "v": ..., ["xk","xv"]} or the
    windowed layout {"k_loc", "v_loc", "k_glob", "v_glob"}."""
    if "k_loc" in cache:
        return decode_windowed(cfg, params, cache, token, pos, rc)
    x = _embed(cfg, params, token, rc)
    if cfg.enc_layers:
        x = x + params["dec_pos"].astype(x.dtype)[pos][None, None]
    win = jnp.asarray(windows(cfg))
    has_cross = cfg.enc_layers > 0
    tables = rope_tables(cfg)

    def body(x, sl):
        rope = None
        if tables is not None:
            sl, rope = sl[:-1], sl[-1]
        if has_cross:
            pl, w, ck, cv, xk, xv = sl
        else:
            pl, w, ck, cv = sl
        x, (ck, cv) = decode_attn_block(cfg, pl["attn"], x, w, ck, cv, pos,
                                        rc, rope=rope)
        if has_cross:
            x = cross_attn_block(cfg, pl["xattn"], x, (xk, xv), rc)
        x, _ = mlp_block(cfg, pl["mlp"], x, rc)
        return x, (ck, cv)

    xs = (params["layers"], win, cache["k"], cache["v"])
    if has_cross:
        xs = xs + (cache["xk"], cache["xv"])
    if tables is not None:
        xs = xs + (tuple(jnp.asarray(t) for t in tables),)
    x, (ck, cv) = jax.lax.scan(body, x, xs)
    new_cache = dict(cache, k=ck, v=cv)
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed(cfg, params, x, rc)
    return logits, new_cache
