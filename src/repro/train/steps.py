"""Step builders: train_step / prefill_step / serve_step (decode).

Each builder returns a StepBundle with the jit'd function, the
ShapeDtypeStruct inputs (for lowering without allocation) and the
in/out NamedShardings — the multi-pod dry-run and the real trainer both
consume the same bundle.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import registry
from repro.models.config import ModelConfig, RunConfig
from repro.optim import adamw
from repro.parallel import shardings as sh


@dataclasses.dataclass
class StepBundle:
    fn: Any                      # jit'd callable
    arg_structs: tuple           # ShapeDtypeStructs (positional)
    in_shardings: tuple
    out_shardings: Any
    init: Callable | None = None  # real-array initializer (smoke tests)

    def lower(self):
        return self.fn.lower(*self.arg_structs)


# ----------------------------------------------------------------- batches

def batch_structs(cfg: ModelConfig, rc: RunConfig, with_labels: bool):
    """ShapeDtypeStructs for one global batch."""
    B, S = rc.global_batch, rc.seq_len
    nmb = rc.num_microbatches
    lead = (nmb, B // nmb) if nmb > 1 else (B,)
    out = {"tokens": jax.ShapeDtypeStruct((*lead, S), jnp.int32)}
    if with_labels:
        out["labels"] = jax.ShapeDtypeStruct((*lead, S), jnp.int32)
    if cfg.enc_layers:
        out["frames"] = jax.ShapeDtypeStruct(
            (*lead, cfg.enc_frames, cfg.d_model), jnp.bfloat16)
    if cfg.n_patches:
        out["patches"] = jax.ShapeDtypeStruct(
            (*lead, cfg.n_patches, cfg.d_model), jnp.bfloat16)
    return out


def batch_logical(cfg: ModelConfig, rc: RunConfig, with_labels: bool):
    nmb = rc.num_microbatches
    lead = (None, "batch") if nmb > 1 else ("batch",)
    out = {"tokens": (*lead, None)}
    if with_labels:
        out["labels"] = (*lead, None)
    if cfg.enc_layers:
        out["frames"] = (*lead, None, None)
    if cfg.n_patches:
        out["patches"] = (*lead, None, None)
    return out


def batch_shardings(cfg, rc, mesh, with_labels):
    logical = batch_logical(cfg, rc, with_labels)
    structs = batch_structs(cfg, rc, with_labels)
    return jax.tree.map(
        lambda lg, s: sh.named(mesh, lg, s.shape), logical, structs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def make_batch(cfg: ModelConfig, rc: RunConfig, key, with_labels=True):
    """Real (host) batch for smoke tests/examples; tiny configs only."""
    structs = batch_structs(cfg, rc, with_labels)
    ks = jax.random.split(key, len(structs))
    out = {}
    for k, (name, s) in zip(ks, structs.items()):
        if s.dtype == jnp.int32:
            out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab, jnp.int32)
        else:
            out[name] = jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
    return out


# ----------------------------------------------------------------- loss

def _ce(logits, labels):
    """Token-mean cross entropy in fp32. logits (B,S,V), labels (B,S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def _ce_chunked(cfg, params, x, labels, rc):
    """Vocab peak-memory-bounded CE: scan over sequence chunks, remat the
    chunk logits in backward. x (B,S,d)."""
    B, S, d = x.shape
    c = rc.chunked_ce
    nc = S // c
    xs = x.reshape(B, nc, c, d).swapaxes(0, 1)
    ls = labels.reshape(B, nc, c).swapaxes(0, 1)

    @jax.checkpoint
    def body(acc, t):
        xc, lc = t
        logits = registry.unembed(cfg, params, xc, rc)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - ll), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return tot / (B * S)


def loss_fn(cfg: ModelConfig, params, batch, rc: RunConfig):
    x, prefix_len, _, _, aux = registry.forward(cfg, params, batch, rc)
    if prefix_len:
        x = x[:, prefix_len:]
    if rc.chunked_ce:
        loss = _ce_chunked(cfg, params, x, batch["labels"], rc)
    else:
        logits = registry.unembed(cfg, params, x, rc)
        loss = _ce(logits, batch["labels"])
    if cfg.is_moe:
        loss = loss + 0.01 * aux
    return loss


def _under_mesh(mesh, fn):
    """`fn` with `mesh` ambient while it is traced (see sh.ambient_mesh)."""
    def traced(*args):
        with sh.ambient_mesh(mesh):
            return fn(*args)
    return traced


# ----------------------------------------------------------------- train

def _param_specs(cfg, rc, defs, mesh, pdt):
    """Parameter shardings, honouring the RunConfig's fsdp policy."""
    import math as _math
    msize = mesh.shape.get("model", 1)
    per_shard = sum(
        _math.prod(d.shape) for d in jax.tree.leaves(
            defs, is_leaf=L.is_def)) * pdt.itemsize // max(1, msize)
    return L.tree_specs(defs, mesh, fsdp=rc.fsdp_enabled(per_shard))


def build_train_step(cfg: ModelConfig, rc: RunConfig, mesh,
                     opt: adamw.AdamWConfig | None = None) -> StepBundle:
    opt = opt or adamw.AdamWConfig()
    pdt = jnp.dtype(rc.param_dtype)
    defs = registry.param_defs(cfg)
    param_structs = L.tree_structs(defs, pdt)
    param_specs = _param_specs(cfg, rc, defs, mesh, pdt)
    opt_structs = adamw.init_state_structs(param_structs)
    opt_specs = {"step": jax.sharding.NamedSharding(
                     mesh, jax.sharding.PartitionSpec()),
                 "m": param_specs, "v": param_specs}
    bstructs = batch_structs(cfg, rc, with_labels=True)
    bspecs = batch_shardings(cfg, rc, mesh, with_labels=True)
    scalar = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    nmb = rc.num_microbatches

    def step(params, opt_state, batch):
        gr_dt = jnp.dtype(rc.grad_reduce_dtype)
        cdt = jnp.dtype(rc.compute_dtype)

        def cast_once(params):
            """Mixed precision: ONE f32->bf16 cast per step (outside the
            layer scan) so (a) the scan reads bf16 weights (half the HBM
            traffic), (b) per-layer grad reduce-scatters run in bf16."""
            if gr_dt == jnp.float32:
                return params
            return jax.tree.map(
                lambda p: p.astype(cdt) if p.dtype == jnp.float32 else p,
                params)

        if nmb == 1:
            loss, grads = jax.value_and_grad(
                partial(loss_fn, cfg, rc=rc))(cast_once(params), batch)
        else:
            cparams = cast_once(params)

            def mb(carry, mbatch):
                l, g = jax.value_and_grad(
                    partial(loss_fn, cfg, rc=rc))(cparams, mbatch)
                acc_l, acc_g = carry
                return (acc_l + l,
                        jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                     acc_g, g)), None

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                mb, (jnp.zeros((), jnp.float32), zero_g), batch)
            loss = loss / nmb
            grads = jax.tree.map(lambda g: g / nmb, grads)
        new_params, new_opt, gnorm = adamw.apply_updates(
            opt, params, grads, opt_state)
        metrics = {"loss": loss, "grad_norm": gnorm}
        return new_params, new_opt, metrics

    fn = jax.jit(
        _under_mesh(mesh, step),
        in_shardings=(param_specs, opt_specs, bspecs),
        out_shardings=(param_specs, opt_specs,
                       {"loss": scalar, "grad_norm": scalar}),
        donate_argnums=(0, 1),
    )

    # jitted with the step's shardings, so each device builds only its
    # own shard and no leaf lands whole on one device first
    @partial(jax.jit, out_shardings=(param_specs, opt_specs))
    def init(key):
        params = L.tree_init(defs, key, pdt)
        return params, adamw.init_state(params)

    return StepBundle(fn, (param_structs, opt_structs, bstructs),
                      (param_specs, opt_specs, bspecs), None, init)


# ----------------------------------------------------------------- prefill

def build_prefill_step(cfg: ModelConfig, rc: RunConfig, mesh) -> StepBundle:
    pdt = jnp.dtype(rc.param_dtype)
    defs = registry.param_defs(cfg)
    param_structs = L.tree_structs(defs, pdt)
    param_specs = _param_specs(cfg, rc, defs, mesh, pdt)
    bstructs = batch_structs(cfg, rc, with_labels=False)
    bspecs = batch_shardings(cfg, rc, mesh, with_labels=False)

    def step(params, batch):
        x, prefix_len, cache, _, _ = registry.forward(
            cfg, params, batch, rc, return_cache=True)
        logits = registry.unembed(cfg, params, x[:, -1:], rc)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    cache_specs = _cache_shardings(cfg, mesh, _prefill_cache_structs(cfg, rc))
    tok_spec = sh.named(mesh, ("batch", None), (rc.global_batch, 1))
    fn = jax.jit(_under_mesh(mesh, step), in_shardings=(param_specs, bspecs),
                 out_shardings=(tok_spec, cache_specs))
    return StepBundle(fn, (param_structs, bstructs),
                      (param_specs, bspecs), None)


def _prefill_cache_structs(cfg, rc):
    """Cache emitted by forward(return_cache=True) as ShapeDtypeStructs."""
    B, S = rc.global_batch, rc.seq_len
    cdt = jnp.dtype(rc.compute_dtype)
    if cfg.family == "transformer":
        S_tot = S + cfg.n_patches
        n, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        c = {"k": jax.ShapeDtypeStruct((n, B, S_tot, Hkv, Dh), cdt),
             "v": jax.ShapeDtypeStruct((n, B, S_tot, Hkv, Dh), cdt)}
        if cfg.enc_layers:
            c["xk"] = jax.ShapeDtypeStruct(
                (n, B, cfg.enc_frames, Hkv, Dh), cdt)
            c["xv"] = jax.ShapeDtypeStruct(
                (n, B, cfg.enc_frames, Hkv, Dh), cdt)
        return c
    spec = registry.init_cache(cfg, B, S, cdt)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], s[1]), spec,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and isinstance(x[0], tuple))


# ----------------------------------------------------------------- decode

def decode_cache_structs(cfg: ModelConfig, rc: RunConfig):
    B, S = rc.global_batch, rc.seq_len
    cdt = jnp.dtype(rc.compute_dtype)
    if cfg.family == "transformer":
        S = S + cfg.n_patches
    spec = registry.init_cache(cfg, B, S, cdt,
                               windowed=rc.windowed_cache)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s[0], s[1]), spec,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and isinstance(x[0], tuple))


def _cache_shardings(cfg, mesh, structs):
    logical = registry.cache_logical(cfg)
    logical = {k: v for k, v in logical.items() if k in structs}
    return jax.tree.map(
        lambda lg, s: sh.named(mesh, lg, s.shape), logical, structs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def build_serve_step(cfg: ModelConfig, rc: RunConfig, mesh) -> StepBundle:
    """One-token decode against a seq_len KV cache."""
    pdt = jnp.dtype(rc.param_dtype)
    defs = registry.param_defs(cfg)
    param_structs = L.tree_structs(defs, pdt)
    param_specs = _param_specs(cfg, rc, defs, mesh, pdt)
    cache_structs = decode_cache_structs(cfg, rc)
    cache_specs = _cache_shardings(cfg, mesh, cache_structs)
    B = rc.global_batch
    tok_struct = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_spec = sh.named(mesh, ("batch", None), (B, 1))
    pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
    scalar = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def step(params, cache, token, pos):
        logits, new_cache = registry.decode(cfg, params, cache, token, pos, rc)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, new_cache

    fn = jax.jit(_under_mesh(mesh, step),
                 in_shardings=(param_specs, cache_specs, tok_spec, scalar),
                 out_shardings=(tok_spec, cache_specs),
                 donate_argnums=(1,))
    return StepBundle(
        fn, (param_structs, cache_structs, tok_struct, pos_struct),
        (param_specs, cache_specs, tok_spec, scalar), None)


def build_step(cfg: ModelConfig, rc: RunConfig, mesh) -> StepBundle:
    if rc.kind == "train":
        return build_train_step(cfg, rc, mesh)
    if rc.kind == "prefill":
        return build_prefill_step(cfg, rc, mesh)
    if rc.kind == "decode":
        return build_serve_step(cfg, rc, mesh)
    raise ValueError(rc.kind)
