"""Top-k MoE FFN with sort-based, capacity-bounded dispatch.

Expert weights are sharded over the "model" mesh axis (expert parallelism).
Dispatch is a sort + scatter into an (E, C, d) buffer so the expert matmuls
are dense batched GEMMs with the *active* flop count (top_k * capacity_factor
x dense-one-expert), unlike one-hot-einsum dispatch which pays all-experts
flops.

Two capacity-bounded dispatch strategies:
  * global (GSPMD): one logical (E, C, d) buffer; the cross-shard scatter
    makes XLA replicate + all-reduce it — simple but collective-heavy;
  * shard-local (shard_map, `rc.shard_moe_tokens`): activations are
    replicated over the "model" axis anyway (TP), so each device routes
    its LOCAL tokens to its LOCAL experts and a psum over "model" combines
    the partial outputs — zero token movement, buffer is (E/mp, C_l, d).
    This is the production layout; EXPERIMENTS.md §Perf quantifies the
    delta against the global baseline.

and, for a device told which experts it holds (`cfg.experts_held`), a
dropless held-expert layer: `held_expert_ffn`, grouped matmuls over the
(token, expert) pairs that fall to its experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.shardings import constrain, get_ambient_mesh


def capacity(cfg, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_ffn(cfg, p, x, rc):
    """x: (T, d) -> (T, d), aux load-balance loss (scalar; a dict for
    held experts, see `held_expert_ffn`)."""
    if cfg.experts_held:
        return held_expert_ffn(cfg, p, x, rc)
    if rc.shard_moe_tokens:
        mesh = get_ambient_mesh()
        if mesh is not None and "model" in mesh.axis_names \
                and cfg.n_experts % mesh.shape["model"] == 0:
            return moe_ffn_sharded(cfg, p, x, rc, mesh)
    return _moe_ffn_global(cfg, p, x, rc)


# ------------------------------------------------------------ shard-local

def _local_dispatch_ffn(cfg, rc, x_l, router, wg, wu, wd, e_off, E_l):
    """Per-device MoE: route local tokens to this device's experts.
    Returns the partial output (sum over local experts) + local aux."""
    cdt = jnp.dtype(rc.compute_dtype)
    T_l, d = x_l.shape
    E, k = cfg.n_experts, cfg.top_k
    C = capacity(cfg, T_l)

    gates = x_l.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(gates, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    flat_e = top_e.reshape(-1)
    mine = (flat_e >= e_off) & (flat_e < e_off + E_l)
    loc_e = jnp.where(mine, flat_e - e_off, E_l)        # E_l = drop bucket
    order = jnp.argsort(loc_e, stable=True)
    sorted_e = loc_e[order]
    counts = jnp.zeros(E_l + 1, jnp.int32).at[loc_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T_l * k, dtype=jnp.int32) - starts[sorted_e]
    keep = (pos < C) & (sorted_e < E_l)
    dest = jnp.where(keep, sorted_e * C + pos, E_l * C)
    tok = order // k

    buf = jnp.zeros((E_l * C + 1, d), cdt).at[dest].set(
        x_l[tok].astype(cdt))
    xe = buf[:E_l * C].reshape(E_l, C, d)
    g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(cdt))
    u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(cdt))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, wd.astype(cdt))

    out_flat = jnp.concatenate(
        [out.reshape(E_l * C, d), jnp.zeros((1, d), cdt)], axis=0)
    gathered = out_flat[dest]
    w = top_p.reshape(-1)[order].astype(cdt)
    y = jnp.zeros((T_l, d), cdt).at[tok].add(gathered * w[:, None])
    return y, aux


def moe_ffn_sharded(cfg, p, x, rc, mesh):
    """shard_map dispatch: tokens stay put; psum over "model" combines the
    per-expert-shard partial outputs (experts ride the TP axis)."""
    import math
    mp = mesh.shape["model"]
    E_l = cfg.n_experts // mp
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = math.prod(mesh.shape[a] for a in dp_axes) if dp_axes else 1
    # tokens sharded over the dp axes iff divisible
    tok_dim = dp_axes if (dp_axes and x.shape[0] % dp_size == 0) else None

    def local(x_l, router, wg, wu, wd):
        e_off = jax.lax.axis_index("model") * E_l
        y, aux = _local_dispatch_ffn(cfg, rc, x_l, router, wg, wu, wd,
                                     e_off, E_l)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, "model")
        if tok_dim:
            aux = jax.lax.pmean(aux, tok_dim)
        return y, aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(tok_dim, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(tok_dim, None), P()),
        check_vma=False)
    return fn(x, p["router"], p["wg"], p["wu"], p["wd"])


# ----------------------------------------------------------- global GSPMD

def _moe_ffn_global(cfg, p, x, rc):
    cdt = jnp.dtype(rc.compute_dtype)
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = capacity(cfg, T)

    # keep tokens data-sharded through the (B,S)->(T,) reshape — without
    # this GSPMD replicates the whole dispatch (observed 21x flops bloat)
    x = constrain(x, ("batch", None))
    gates = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(gates, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                 # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # load-balance aux (Switch-style): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    flat_e = top_e.reshape(-1)                              # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # E*C = drop slot
    tok = order // k

    buf = jnp.zeros((E * C + 1, d), cdt).at[dest].set(x[tok].astype(cdt))
    xe = constrain(buf[: E * C].reshape(E, C, d), ("model", None, None))

    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(cdt))
    u = jnp.einsum("ecd,edf->ecf", xe, p["wu"].astype(cdt))
    h = jax.nn.silu(g) * u
    h = constrain(h, ("model", None, None))
    out = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(cdt))
    out = constrain(out, ("model", None, None))

    out_flat = jnp.concatenate(
        [out.reshape(E * C, d), jnp.zeros((1, d), cdt)], axis=0)
    gathered = out_flat[dest]                               # (T*k, d)
    w = top_p.reshape(-1)[order].astype(cdt)
    y = jnp.zeros((T, d), cdt).at[tok].add(gathered * w[:, None])
    return y, aux


# ------------------------------------------------------------ held experts

def route(cfg, x, router):
    """Top-k routing over all n_experts: each token's k experts (T, k),
    their softmax weights renormalized over the k, and the Switch
    load-balance term. The router's product runs in float32 at full
    precision, so that near ties split as the float32 reference splits
    them wherever the inputs agree."""
    E = cfg.n_experts
    gates = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(gates, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    return top_p, top_e, E * jnp.sum(me * ce)


def held_expert_ffn(cfg, p, x, rc):
    """The part of each token's result that this device's experts give:
    experts [expert_offset, expert_offset + experts_held) of the
    n_experts the router chooses among (expert parallelism, without the
    exchange). Every token is routed over all experts (softmax, top-k,
    the k weights renormalized); the (token, expert) pairs that fall to a
    held expert are sorted by expert and run through grouped matmuls
    (`kernels.ops.gmm`), which compute each held expert's rows and none
    of the others'. No pair is dropped, however unbalanced the routing.
    Returns the weighted sum over the held pairs, and {"balance": the
    Switch load-balance term over all experts, "held_pairs": the pairs
    the held experts computed}."""
    from repro.kernels import ops as kops
    cdt = jnp.dtype(rc.compute_dtype)
    T, d = x.shape
    k, H, off = cfg.top_k, cfg.experts_held, cfg.expert_offset
    top_p, top_e, aux = route(cfg, x, p["router"])

    # group g < H is held expert off + g; group H, the pairs held elsewhere
    flat_e = top_e.reshape(-1)
    held = (flat_e >= off) & (flat_e < off + H)
    group = jnp.where(held, flat_e - off, H)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros(H + 1, jnp.int32).at[group].add(1)
    xs = x.astype(cdt)[order // k]                          # (T*k, d)
    g = kops.gmm(xs, p["wg"].astype(cdt), sizes, out_dtype=cdt)
    u = kops.gmm(xs, p["wu"].astype(cdt), sizes, out_dtype=cdt)
    h = jax.nn.silu(g) * u
    out = kops.gmm(h, p["wd"].astype(cdt), sizes, out_dtype=cdt)
    # back to (token, choice) order; pairs held elsewhere weigh 0
    out = out[jnp.argsort(order)].reshape(T, k, d)
    w = jnp.where(held, top_p.reshape(-1), 0.0).reshape(T, k)
    y = jnp.einsum("tkd,tk->td", out, w.astype(cdt),
                   preferred_element_type=jnp.float32)
    return y.astype(cdt), {"balance": aux,
                           "held_pairs": jnp.sum(sizes[:H])}
