"""Back-to-back checkpoint saves of one rank's shard of a training state.

The state is what the trainer checkpoints, {"params", "opt": {"step", "m",
"v"}}, at the shapes one rank of an FSDP job holds: the repo's FSDP rule
applied to the model's parameters on a (data, model) = (N, 1) mesh. It is
made on the device from the seed, in one jitted call. Each operation is
one `CheckpointManager.save` of the next step followed by
`retain(keep)`.
"""
from __future__ import annotations

import json

import numpy as np

from chipbench import costs, model, registry
from chipbench.reference import stripes


def shard_shapes(cfg: dict) -> dict:
    """{"params": tree, "opt": {"step": (), "m": tree, "v": tree}} of
    shapes, as one rank of the FSDP mesh holds them."""
    import jax
    from jax.sharding import AbstractMesh
    from repro.models import layers as L
    from repro.models import registry as mreg
    dp = cfg["training"]["fsdp_data_parallel"]
    defs = mreg.param_defs(registry.arch(cfg).model_config(cfg))
    specs = L.tree_specs(defs, AbstractMesh((dp, 1), ("data", "model")),
                         fsdp=True)
    shapes = jax.tree.map(lambda d, s: s.shard_shape(d.shape), defs, specs,
                          is_leaf=L.is_def)
    return {"params": shapes, "opt": {"step": (), "m": shapes, "v": shapes}}


def make_state(shapes: dict, seed: int):
    """The state from the seed, on the device: one jitted call."""
    import jax
    import jax.numpy as jnp

    is_shape = lambda x: isinstance(x, tuple)     # noqa: E731
    flat, tdef = jax.tree.flatten(shapes, is_leaf=is_shape)
    kinds = []
    for path, _ in jax.tree.flatten_with_path(shapes, is_leaf=is_shape)[0]:
        keys = [getattr(p, "key", None) for p in path]
        kinds.append("step" if keys[-1] == "step" else
                     "v" if "v" in keys[:2] else
                     "m" if "m" in keys[:2] else "p")

    @jax.jit
    def gen(key):
        out = []
        for i, (shape, kind) in enumerate(zip(flat, kinds)):
            k = jax.random.fold_in(key, i)
            if kind == "step":
                out.append(jnp.asarray(1000, jnp.int32))
            elif kind == "v":
                out.append(jax.random.uniform(k, shape, jnp.float32) * 1e-6)
            else:
                scale = 1e-3 if kind == "m" else 0.02
                out.append(jax.random.normal(k, shape, jnp.float32) * scale)
        return out

    key = jax.random.PRNGKey(model.seed32(seed))
    return jax.tree.unflatten(tdef, gen(key))


def leaf_paths(tree, prefix=()):
    """(name, leaf) in the order and with the names a save gives them."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from leaf_paths(tree[k], prefix + (str(k),))
    else:
        yield ".".join(prefix), tree


class Cell:
    op_label = "save"

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg = config
        self.tr = traffic
        self.seed = seed
        self.steps: list[int] = []

    def setup(self):
        import jax
        from repro.ckpt import CheckpointManager
        from repro.core import LustreCluster
        from repro.fsio import LustreClient
        cl, ck = self.cfg["cluster"], self.cfg["checkpoint"]
        self.shapes = shard_shapes(self.cfg)
        self.state = jax.block_until_ready(make_state(self.shapes, self.seed))
        self.leaves = [(n, a.shape, a.dtype, a.nbytes)
                       for n, a in leaf_paths(self.state)]
        self.state_bytes = sum(n for *_, n in self.leaves)
        self.cluster = LustreCluster(osts=cl["osts"], mdses=cl["mdses"],
                                     clients=cl["clients"],
                                     commit_interval=cl["commit_interval"])
        n_clients = cl["clients"]
        self.writers = [LustreClient(self.cluster, i % n_clients).mount()
                        for i in range(ck["writers"])]
        self.mgr = CheckpointManager(
            self.writers, ck["base"], parity=ck["parity"],
            stripe_count=min(ck["stripe_count"], cl["osts"]),
            stripe_size=ck["stripe_size"])
        from repro.ckpt import checkpoint as ckpt_mod
        self._piece = ckpt_mod.SAVE_PIECE_BYTES
        self._save(0)                 # compiles the save's shapes

    def _save(self, step: int):
        self.mgr.save(step, self.state, extra_meta={"arch": self.cfg["name"]})
        self.mgr.retain(keep=self.tr["retain_keep"])
        self.steps.append(step)

    def op(self, i: int) -> dict:
        self._save(i + 1)
        return {"write_bytes": self.state_bytes}

    def counters(self) -> dict:
        c = dict(self.cluster.stats.counters)
        c["chipbench.saves"] = len(self.steps)
        return c

    def kernel_bytes(self, before: dict, after: dict) -> dict:
        saves = after["chipbench.saves"] - before["chipbench.saves"]
        if not self.cfg["checkpoint"]["parity"] or not saves:
            return {}
        ck = self.cfg["checkpoint"]
        cnt = min(ck["stripe_count"], self.cfg["cluster"]["osts"])
        per = sum(costs.ckpt_parity_kernel_bytes(
            n, ck["stripe_size"], cnt, self._piece) for *_, n in self.leaves)
        return {"xor_parity": saves * per}

    def finish(self):
        self.state = None

    # ------------------------------------------------------------ verify
    def _object_bytes(self, fs, path: str) -> tuple[list, object]:
        fh = fs.open(path)
        lsm = fh.lsm
        fs.close(fh)
        out = []
        for o in lsm.objects:
            obj = self.cluster.target(o["ost"]).obd.objects.get(
                (o["group"], o["oid"]))
            out.append(np.frombuffer(bytes(obj.data), np.uint8)
                       if obj is not None else np.zeros(0, np.uint8))
        return out, lsm

    def verify(self) -> list:
        """The last save as it lies on the OSTs, against the state made
        again from the seed: every leaf file's objects, every parity file
        and the manifest. Earlier saves must be gone (retain)."""
        import jax
        ck = self.cfg["checkpoint"]
        cnt = min(ck["stripe_count"], self.cfg["cluster"]["osts"])
        ssz = ck["stripe_size"]
        fs = self.writers[0]
        step = self.steps[-1]
        d = f"{ck['base']}/step_{step:08d}"
        want_state = make_state(self.shapes, self.seed)
        leaf_wrong = parity_wrong = 0
        want_leaves = {}
        for w_idx, (name, arr) in enumerate(leaf_paths(want_state)):
            data = np.asarray(jax.device_get(arr)).reshape(-1).view(np.uint8)
            want_leaves[name] = {
                "shape": list(arr.shape), "dtype": str(arr.dtype),
                "bytes": int(data.size),
                "writer": w_idx % len(self.writers)}
            if ck["parity"] and data.size:
                want_leaves[name]["parity"] = True
            got, lsm = self._object_bytes(fs, f"{d}/{name}.bin")
            if (lsm.stripe_size, lsm.stripe_count) != (ssz, cnt):
                leaf_wrong += data.size
                continue
            for g, w in zip(got, stripes.objects(data, ssz, cnt)):
                leaf_wrong += stripes.count_wrong(g, w)
            if ck["parity"] and data.size:
                pgot, _ = self._object_bytes(fs, f"{d}/{name}.parity")
                parity_wrong += stripes.count_wrong(
                    np.concatenate(pgot) if pgot else np.zeros(0, np.uint8),
                    stripes.column_parity(data, ssz, cnt))
        mobjs, _ = self._object_bytes(fs, f"{d}/MANIFEST.json")
        try:
            manifest = json.loads(np.concatenate(mobjs).tobytes())
        except ValueError:
            manifest = {}
        want = {"step": step, "leaves": want_leaves,
                "arch": self.cfg["name"]}
        manifest_wrong = sum(manifest.get(k) != v for k, v in want.items()
                             if k != "leaves")
        got_leaves = manifest.get("leaves", {})
        manifest_wrong += sum(got_leaves.get(n) != e
                              for n, e in want_leaves.items())
        manifest_wrong += len(set(got_leaves) - set(want_leaves))
        stale = sum(1 for n in fs.readdir(ck["base"])
                    if n.startswith("step_") and n != f"step_{step:08d}")
        return [("leaf_bytes_wrong", leaf_wrong, 0),
                ("parity_bytes_wrong", parity_wrong, 0),
                ("manifest_fields_wrong", manifest_wrong, 0),
                ("stale_steps_left", stale, 0)]
