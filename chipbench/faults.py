"""Broken versions of the timed path, to show that `correct` catches them.

FAULTS are what a cell's comparison must catch, each cell those it can
have: an operation that leaves the state as it was, half of the work left
out, the exchange between chips left out, and an answer or a token altered
where it is produced. CONTROLS are the shortcuts a later change might be
tempted to take, each breaking a guarantee the cell's configuration
states. Each is a set of replacements for `patches.replaced`, planted for
the window only (`harness.run_cell`).
"""
from __future__ import annotations

import itertools


def _write_noop(orig):
    def write(self, fh, data, offset=None, gid=0):
        return len(data)
    return write


def _every_other(orig, skipped):
    n = itertools.count()

    def call(self, fh, arg, *a, **kw):
        if next(n) % 2:
            return skipped(arg)
        return orig(self, fh, arg, *a, **kw)
    return call


def _flip_first_bit(orig):
    def xor_parity(*a, **kw):
        out = orig(*a, **kw)
        return out.at[0].set(out[0] ^ 1)
    return xor_parity


def _parity_of_touched_units(orig):
    # the round's units a write does not touch are taken as empty: no
    # read-modify-write, and the parity covers the touched units only
    def unit(self, lsm, r, i):
        return b""
    return unit


def _no_reconstruct(orig):
    # a lost unit is returned as zeros instead of being rebuilt
    def rebuild(self, lsm, r, dead):
        return bytes(lsm.stripe_size)
    return rebuild


def _int8_checkpoint(orig):
    # the program's own lower-precision path: leaves stored as int8
    def save(self, *a, **kw):
        kept, self.quantize = self.quantize, "int8"
        try:
            return orig(self, *a, **kw)
        finally:
            self.quantize = kept
    return save


def _step_unchanged(orig):
    # the optimizer hands back the weights and its state as they were
    def apply_updates(cfg, params, grads, state):
        _, _, gnorm = orig(cfg, params, grads, state)
        return params, state, gnorm
    return apply_updates


def _half_batch(orig):
    def loss_fn(cfg, params, batch, rc):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return orig(cfg, params, half, rc)
    return loss_fn


def _exchange_left_out(orig):
    # FSDP without the gradients' exchange between chips: each chip
    # updates its shard of a leaf with the gradient of the mean loss over
    # its own block of the batch alone (a leaf no chip shards takes chip
    # 0's); the loss reported is still the mean over the whole batch
    def loss_fn(cfg, params, batch, rc):
        import jax
        import jax.numpy as jnp
        from repro.models import layers as L
        from repro.models import registry as mreg
        from repro.parallel import shardings as sh
        mesh = sh.get_ambient_mesh()
        n = mesh.shape["data"]
        specs = L.tree_specs(mreg.param_defs(cfg), mesh, fsdp=True)
        per = batch["tokens"].shape[0] // n

        def own(x, spec, k):
            dims = [i for i, a in enumerate(spec.spec) if a == "data"]
            if not dims:
                return x if k == 0 else jax.lax.stop_gradient(x)
            shard = jax.lax.broadcasted_iota(jnp.int32, x.shape, dims[0]) \
                // (x.shape[dims[0]] // n)
            return jnp.where(shard == k, x, jax.lax.stop_gradient(x))

        total = mean = 0.0
        for k in range(n):
            lk = orig(cfg, jax.tree.map(lambda x, s: own(x, s, k), params,
                                        specs),
                      {key: v[k * per:(k + 1) * per]
                       for key, v in batch.items()}, rc)
            total = total + lk
            mean = mean + jax.lax.stop_gradient(lk) / n
        return total - jax.lax.stop_gradient(total) + mean
    return loss_fn


def _token_altered(orig):
    def batch_at(self, step):
        out = orig(self, step)
        out[0, 0] ^= 1
        return out
    return batch_at


FAULTS = {
    "write_unchanged": {
        "repro.fsio.client:LustreClient.write": _write_noop},
    "half_writes_left_out": {
        "repro.fsio.client:LustreClient.write":
            lambda o: _every_other(o, len)},
    "half_reads_left_out": {
        "repro.fsio.client:LustreClient.read":
            lambda o: _every_other(o, lambda n: b"\0" * n)},
    "parity_altered": {
        "repro.kernels.ops:xor_parity": _flip_first_bit},
    "step_unchanged": {
        "repro.optim.adamw:apply_updates": _step_unchanged},
    "half_batch_left_out": {
        "repro.train.steps:loss_fn": _half_batch},
    "exchange_left_out": {
        "repro.train.steps:loss_fn": _exchange_left_out},
    "token_altered": {
        "repro.data.pipeline:TokenPipeline.batch_at": _token_altered},
}

CONTROLS = {
    "raid5_parity_of_touched_units": {
        "repro.core.lov:Lov._r5_unit_data": _parity_of_touched_units},
    "raid5_no_reconstruct": {
        "repro.core.lov:Lov._r5_rebuild_slot_unit": _no_reconstruct},
    "ckpt_int8": {
        "repro.ckpt.checkpoint:CheckpointManager.save": _int8_checkpoint},
}


def named(name: str) -> dict:
    if name in FAULTS:
        return FAULTS[name]
    if name in CONTROLS:
        return CONTROLS[name]
    raise KeyError(f"no fault or control named {name!r}")
