"""Fault-tolerant trainer: JAX training loop over the Lustre substrate.

End-to-end integration of the paper's storage architecture with a real
training job:
  * data: deterministic sharded TokenPipeline reading a striped corpus;
  * checkpoints: CheckpointManager (striped, parity-coded, crash-consistent
    manifests) — save every `ckpt_every`, `Trainer.resume()` restores the
    latest complete checkpoint and continues at the exact step;
  * fault tolerance: OST/MDS failures during the run surface as timeouts
    inside the storage clients and recover transparently (failover ring /
    replay); a *trainer* death is recovered by constructing a fresh Trainer
    and calling resume();
  * elasticity: resume() re-shards the restored arrays onto whatever mesh
    the new trainer has (shapes come from the manifest, placement from the
    new step bundle);
  * straggler mitigation: batch reads fan out over stripes; a slow OST
    link delays only its stripe, and hedged reads (mirror path) cap the
    tail when RAID1 mirrors exist.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.core import metrics
from repro.core.cluster import LustreCluster
from repro.data import TokenDataset, TokenPipeline
from repro.fsio import LustreClient
from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig, RunConfig
from repro.train import steps as steps_mod


@dataclasses.dataclass
class TrainerConfig:
    model: ModelConfig
    rc: RunConfig
    n_steps: int = 50
    ckpt_every: int = 10
    ckpt_base: str = "/ckpt"
    data_path: str = "/data/tokens.bin"
    n_writers: int = 2
    parity: bool = True
    dataset_seqs: int = 2048
    seed: int = 0


class Trainer:
    def __init__(self, cluster: LustreCluster, cfg: TrainerConfig,
                 mesh=None):
        self.cluster = cluster
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_host_mesh()
        self.bundle = steps_mod.build_train_step(cfg.model, cfg.rc, self.mesh)
        # storage clients: writer 0 is also the data-plane reader
        n_clients = len(cluster.client_nodes)
        self.writers = [LustreClient(cluster, i % n_clients).mount()
                        for i in range(cfg.n_writers)]
        self.fs = self.writers[0]
        self.ckpt = CheckpointManager(
            self.writers, cfg.ckpt_base, parity=cfg.parity,
            stripe_count=min(3, len(cluster.ost_targets)),
            stripe_size=1 << 18)
        self.dataset = TokenDataset(
            self.fs, cfg.data_path, vocab=cfg.model.vocab,
            seq_len=cfg.rc.seq_len, n_seqs=cfg.dataset_seqs,
            seed=cfg.seed).build()
        gb = cfg.rc.global_batch
        self.pipeline = TokenPipeline(self.fs, self.dataset, dp_rank=0,
                                      dp_size=1, batch_per_rank=gb,
                                      seed=cfg.seed)
        self.step = 0
        self.params = None
        self.opt_state = None
        self.metrics: list[dict] = []

    # ---------------------------------------------------------------- init
    def init_state(self):
        params, opt = self.bundle.init(jax.random.PRNGKey(self.cfg.seed))
        self.params, self.opt_state = params, opt
        return self

    # ---------------------------------------------------------------- data
    @metrics.spanned("train.batch")
    def _batch(self, step: int) -> dict:
        toks = self.pipeline.batch_at(step)
        b = {"tokens": jax.numpy.asarray(toks)}
        # next-token labels within the stored sequence
        lab = np.roll(toks, -1, axis=-1)
        lab[:, -1] = 0
        b["labels"] = jax.numpy.asarray(lab)
        rc = self.cfg.rc
        if rc.num_microbatches > 1:
            nmb = rc.num_microbatches
            b = {k: v.reshape(nmb, v.shape[0] // nmb, *v.shape[1:])
                 for k, v in b.items()}
        cfgm = self.cfg.model
        key = jax.random.PRNGKey(step)
        lead = b["tokens"].shape[:-1]
        if cfgm.enc_layers:
            b["frames"] = jax.random.normal(
                key, (*lead, cfgm.enc_frames, cfgm.d_model),
                jax.numpy.bfloat16)
        if cfgm.n_patches:
            b["patches"] = jax.random.normal(
                key, (*lead, cfgm.n_patches, cfgm.d_model),
                jax.numpy.bfloat16)
        return b

    # ---------------------------------------------------------------- loop
    def run(self, n_steps: int | None = None, *, fail_at: dict | None = None
            ) -> list[dict]:
        """Train. `fail_at` maps step -> callable(cluster) fault injection
        (e.g. lambda c: c.fail_node('ost1'))."""
        n = n_steps if n_steps is not None else self.cfg.n_steps
        if self.params is None:
            self.init_state()
        end = self.step + n
        while self.step < end:
            if fail_at and self.step in fail_at:
                fail_at[self.step](self.cluster)
            batch = self._batch(self.step)
            t0 = time.perf_counter()
            self.params, self.opt_state, m = jax.block_until_ready(
                self.bundle.fn(self.params, self.opt_state, batch))
            self.step += 1
            rec = {"step": self.step, "loss": float(m["loss"]),
                   "grad_norm": float(m["grad_norm"]),
                   "step_s": time.perf_counter() - t0}
            self.metrics.append(rec)
            if self.step % self.cfg.ckpt_every == 0 or self.step == end:
                t0 = time.perf_counter()
                self.save_checkpoint()
                rec["save_s"] = time.perf_counter() - t0
        return self.metrics

    # ---------------------------------------------------------- checkpoint
    def _state_tree(self) -> dict:
        """The state to checkpoint, as device arrays: the save copies
        them to the host a piece at a time."""
        return {"params": self.params, "opt": self.opt_state}

    def save_checkpoint(self):
        self.ckpt.save(self.step, self._state_tree(),
                       extra_meta={"arch": self.cfg.model.name})

    @classmethod
    def resume(cls, cluster: LustreCluster, cfg: TrainerConfig,
               mesh=None) -> "Trainer":
        """Fresh trainer (possibly a different mesh — elastic) restored
        from the latest complete checkpoint."""
        t = cls(cluster, cfg, mesh)
        t.ckpt.cleanup_incomplete()
        flat, manifest = t.ckpt.restore()
        t.step = manifest["step"]

        param_structs, opt_structs, _ = t.bundle.arg_structs
        pspecs, ospecs, _ = t.bundle.in_shardings

        def build(prefix, structs, specs):
            leaves_s = jax.tree.leaves_with_path(structs)
            leaves_p = jax.tree.leaves_with_path(specs)
            out_leaves = []
            for (path, s), (_, spec) in zip(leaves_s, leaves_p):
                name = prefix + ".".join(
                    _path_key(p) for p in path)
                arr = flat[name].astype(s.dtype)
                out_leaves.append(jax.device_put(arr, spec))
            return jax.tree.unflatten(
                jax.tree.structure(structs), out_leaves)

        t.params = build("params.", param_structs, pspecs)
        t.opt_state = build("opt.", opt_structs, ospecs)
        return t


def _path_key(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)
