"""Training steps through the trainer's jitted step, each batch read from
a token corpus on the file system by the trainer's pipeline.

The trainer runs on the cell's chips (`chips` in `BENCHMARK.json`, which
the harness sets on the cell): a (data = chips, model = 1) mesh, and a
step of the configuration's `training.global_batch` sequences a chip.
The model is the configuration's architecture, `archs/<architecture>.py`.
Set-up writes the corpus (`corpus_seqs` sequences drawn from the seed)
through the file system, builds the `Trainer` over it, puts weights made
by the benchmark from the seed in place of the trainer's own, and drives
the first `checked_steps` steps through the window's own call: they
compile the step, and their losses, the first gradient and the change of
the weights are what the reference checks. The window continues from
there, one step per operation, and never rereads a sequence. No
checkpoint is saved.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import model, registry
from chipbench.reference import adamw as ref_adamw


def param_kinds(tree, norm_leaves) -> list:
    """Per leaf (in flatten order): "norm" for the RMSNorm gains the
    architecture names in `norm_leaves`, else "matrix"."""
    import jax
    out = []
    for path, _ in jax.tree.flatten_with_path(tree)[0]:
        name = getattr(path[-1], "key", "")
        out.append("norm" if name in norm_leaves else "matrix")
    return out


def make_gen(structs, norm_leaves, shardings=None):
    """A jitted `gen(key)` giving weights of the trainer's tree: matrices
    and the embedding ~ N(0, 0.02), RMSNorm gain offsets ~ N(0, 0.1)."""
    import jax
    import jax.numpy as jnp
    flat, tdef = jax.tree.flatten(structs)
    kinds = param_kinds(structs, norm_leaves)

    def gen(key):
        out = []
        for i, (s, kind) in enumerate(zip(flat, kinds)):
            std = 0.1 if kind == "norm" else 0.02
            out.append(jax.random.normal(jax.random.fold_in(key, i),
                                         s.shape, jnp.float32) * std)
        return jax.tree.unflatten(tdef, out)

    return jax.jit(gen, out_shardings=shardings)


def leaf_norms(tree) -> list[float]:
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])
    return [float(x) for x in fn(tree)]


def delta_norms(gen, key, params) -> list[float]:
    """Norm of each leaf's change from the weights `gen(key)` made."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, k: [jnp.sqrt(jnp.sum(jnp.square(a - b)))
                               for a, b in zip(jax.tree.leaves(p),
                                               jax.tree.leaves(gen(k)))])
    return [float(x) for x in fn(params, key)]


def worst_gap(got: list, want: list, floor_share: float = 1e-3):
    """Worst leaf of |got - want| over max(want's leaf, want's median
    leaf), leaving out leaves whose reference reading is under
    `floor_share` of the median leaf's (they move by round-off alone)."""
    med = float(np.median(want))
    gaps = [abs(g - w) / max(w, med) for g, w in zip(got, want)
            if w >= floor_share * med]
    return max(gaps) if gaps else 0.0


class Cell:
    op_label = "step"
    # the checked steps run in set-up, so a planted fault covers set-up
    checked_in_setup = True
    chips = 1                          # the harness sets the cell's own

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg = config
        self.tr_cfg = traffic
        self.seed = seed
        self.batches: list = []        # host tokens of every step
        self.readings: dict = {}

    def setup(self):
        import jax
        from repro.core import LustreCluster
        from repro.fsio import LustreClient
        from repro.launch.mesh import make_host_mesh
        from repro.models.config import RunConfig
        from repro.train.trainer import Trainer, TrainerConfig
        t = self.tr_cfg
        cl, tc = self.cfg["cluster"], self.cfg["training"]
        self.arch = registry.arch(self.cfg)
        self.devices = jax.devices()[:self.chips]
        self.seq = tc["seq_length"]
        self.batch = tc["global_batch"] * self.chips
        self.cluster = LustreCluster(osts=cl["osts"], mdses=cl["mdses"],
                                     clients=cl["clients"],
                                     commit_interval=cl["commit_interval"])
        self._write_corpus(LustreClient(self.cluster,
                                        cl["clients"] - 1).mount())
        mcfg = self.arch.model_config(self.cfg)
        rc = RunConfig(seq_len=self.seq, global_batch=self.batch,
                       kind="train", param_dtype=tc["param_dtype"],
                       compute_dtype=tc["compute_dtype"], attn_impl="ref")
        tcfg = TrainerConfig(model=mcfg, rc=rc, ckpt_every=1 << 62,
                             data_path=t["corpus_path"],
                             dataset_seqs=t["corpus_seqs"],
                             seed=model.seed32(self.seed))
        self.tr = tr = Trainer(self.cluster, tcfg,
                               mesh=make_host_mesh(devices=self.devices))
        self.notes = {"mesh": dict(tr.mesh.shape),
                      "sequences_per_step": self.batch}
        self._check_optimizer(tr)
        pstructs, ostructs, _ = tr.bundle.arg_structs
        pspecs, ospecs, _ = tr.bundle.in_shardings
        self.pspecs = pspecs
        self.gen = make_gen(pstructs, self.arch.NORM_LEAVES, pspecs)
        self.key = jax.random.PRNGKey(model.seed32(self.seed + 1))
        tr.params = self.gen(self.key)
        if any(s.dtype != jax.numpy.float32
               for s in jax.tree.leaves(pstructs)):
            # the program's lower-precision path: the same weights, rounded
            tr.params = jax.jit(lambda p: jax.tree.map(
                lambda x, s: x.astype(s.dtype), p, pstructs),
                out_shardings=pspecs)(tr.params)
        tr.opt_state = jax.jit(
            lambda: jax.tree.map(lambda s: jax.numpy.zeros(s.shape, s.dtype),
                                 ostructs), out_shardings=ospecs)()
        self.tokens_per_step = self.seq * self.batch
        self.next_step = 0
        n = t["checked_steps"]
        for i in range(n):
            self.op(i, record=True)
            if i == 0:
                b1 = self.cfg["training"]["optimizer"]["b1"]
                self.readings["grad_norms"] = [
                    x / (1 - b1) for x in leaf_norms(tr.opt_state["m"])]
        self.readings["delta_norms"] = delta_norms(self.gen, self.key,
                                                   tr.params)

    def _check_optimizer(self, tr):
        # the program has to run the optimizer the configuration states
        import dataclasses
        from repro.optim import adamw
        got = dataclasses.asdict(adamw.AdamWConfig())
        want = self.cfg["training"]["optimizer"]
        for k, v in want.items():
            if k in got and got[k] != v:
                raise ValueError(f"the trainer's AdamW {k}={got[k]}, the "
                                 f"configuration states {v}")

    def _write_corpus(self, fs):
        rng = np.random.default_rng(self.seed)
        n, v = self.tr_cfg["corpus_seqs"], self.cfg["vocab_size"]
        self.corpus = rng.integers(0, v, (n, self.seq), dtype=np.int32)
        path = self.tr_cfg["corpus_path"]
        fs.mkdir_p(path.rsplit("/", 1)[0])
        fh = fs.creat(path)
        step = 256
        for a in range(0, n, step):
            fs.write(fh, self.corpus[a:a + step].tobytes(),
                     offset=a * self.seq * 4)
        fs.close(fh)

    def op(self, i: int, record: bool = False) -> dict:
        """One training step: the step after the last one taken, whatever
        the window's operation count `i`."""
        import jax
        tr = self.tr
        t0 = time.perf_counter()
        batch = tr._batch(self.next_step)
        self.next_step += 1
        self.batches.append(np.asarray(batch["tokens"]))
        t1 = time.perf_counter()
        tr.params, tr.opt_state, m = jax.block_until_ready(
            tr.bundle.fn(tr.params, tr.opt_state, batch))
        if record:
            self.readings.setdefault("losses", []).append(float(m["loss"]))
        return {"tokens": self.tokens_per_step, "data_s": t1 - t0}

    def counters(self) -> dict:
        return dict(self.cluster.stats.counters)

    def kernel_bytes(self, before: dict, after: dict) -> dict:
        return {}

    def finish(self):
        self.tr.params = self.tr.opt_state = None

    # ------------------------------------------------------------ verify
    def _batch_rows_wrong(self) -> int:
        rows = {r.tobytes() for r in self.corpus}
        seen, wrong = set(), 0
        for b in self.batches:
            for r in b:
                key = r.tobytes()
                wrong += key not in rows or key in seen
                seen.add(key)
        return wrong

    def _block_grads(self, cfg_items, tdef, leaves, tokens):
        """The loss and gradient leaves of one step's batch, as the mean
        over blocks of `training.global_batch` sequences: block k runs on
        the cell's device k (mod their number) on its own copy of the
        weights, and the mean is taken on the first device, leaf by leaf."""
        import jax
        per = self.cfg["training"]["global_batch"]
        n = len(tokens) // per
        outs = []
        for k in range(n):
            d = self.devices[k % len(self.devices)]
            val, grads, _ = self.arch.loss_and_grads(
                cfg_items, jax.device_put(jax.tree.unflatten(tdef, leaves), d),
                jax.device_put(tokens[k * per:(k + 1) * per], d))
            outs.append((val, jax.tree.leaves(grads)))
            del grads
        loss = sum(float(v) for v, _ in outs) / n
        parts = [g for _, g in outs]
        del outs
        mean = []
        for j in range(len(leaves)):
            g = parts[0][j]
            for p in parts[1:]:
                g = g + jax.device_put(p[j], self.devices[0])
            mean.append(g / n)
            for p in parts:
                p[j] = None
        return loss, mean

    def reference(self) -> dict:
        """The reference's readings over the checked steps: losses, the
        first clipped gradient's leaf norms, the weights' change."""
        import jax
        import jax.numpy as jnp
        opt = self.cfg["training"]["optimizer"]
        cfg_items = tuple(sorted(
            (k, v) for k, v in self.cfg.items()
            if isinstance(v, (int, float, str)) and not isinstance(v, bool)))
        leaves, tdef = jax.tree.flatten(
            jax.device_put(self.gen(self.key), self.devices[0]))
        ms = [None] * len(leaves)
        vs = [None] * len(leaves)
        out = {"losses": []}
        for s in range(self.tr_cfg["checked_steps"]):
            val, gl = self._block_grads(cfg_items, tdef, leaves,
                                        self.batches[s])
            out["losses"].append(val)
            gnorm = sum(float(jnp.sum(g * g)) for g in gl) ** 0.5
            scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-12))
            if s == 0:
                out["grad_norms"] = [float(jnp.linalg.norm(g)) * scale
                                     for g in gl]
            step = s + 1
            lr = opt["lr"] * min(1.0, step / opt["warmup_steps"])
            for j in range(len(leaves)):
                m = jnp.zeros_like(gl[j]) if ms[j] is None else \
                    jnp.asarray(ms[j])
                v = jnp.zeros_like(gl[j]) if vs[j] is None else \
                    jnp.asarray(vs[j])
                leaves[j], m, v = ref_adamw.adamw_leaf(
                    leaves[j], gl[j], m, v, lr=lr, scale=scale,
                    bc1=1 - opt["b1"] ** step, bc2=1 - opt["b2"] ** step,
                    b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                    wd=opt["weight_decay"])
                ms[j], vs[j] = np.asarray(m), np.asarray(v)
                gl[j] = None
        out["delta_norms"] = delta_norms(
            self.gen, self.key,
            jax.device_put(jax.tree.unflatten(tdef, leaves), self.pspecs))
        return out

    def verify(self) -> list:
        """The program's checked steps against the float32 reference, and
        every batch the window read against the corpus. A gap the mix
        gives no limit for is not compared (it has no reading of a
        control or fault to set one from) and goes to the notes."""
        limits = self.tr_cfg["limits"]
        want = self.reference()
        got = self.readings
        gaps = {"loss_gap": max(abs(g - w) / abs(w) for g, w in
                                zip(got["losses"], want["losses"])),
                "grad_norm_gap": worst_gap(got["grad_norms"],
                                           want["grad_norms"]),
                "update_norm_gap": worst_gap(got["delta_norms"],
                                             want["delta_norms"])}
        self.notes["not_compared"] = {k: v for k, v in gaps.items()
                                      if k not in limits}
        return [(k, v, limits[k]) for k, v in gaps.items() if k in limits] \
            + [("batch_rows_wrong", self._batch_rows_wrong(), 0)]
