"""Distributed striped checkpointing over the Lustre substrate.

This is the paper's architecture doing the job it does in real ML clusters:
checkpoints live on Lustre. Design:

  * one file per pytree leaf, striped over OSTs (LOV, ch. 10); writers are
    N LustreClients (one per simulated host / dp group) writing in
    parallel — group locks (ch. 10.10) let cooperating writers share
    objects without PW ping-pong;
  * crash consistency: data files first, MANIFEST.json last (the commit
    record). restore() only trusts steps with a manifest; incomplete step
    directories are garbage (client died mid-save) and are removed by
    `cleanup_incomplete` — the client-side mirror of the MDS orphan logic;
  * erasure coding (ch. 15 adapted): optional XOR parity file per tensor,
    computed by the Pallas parity kernel; `restore` can reconstruct a
    stripe lost to a dead OST's disk;
  * elastic restore: the manifest stores shapes/dtypes; restore returns
    numpy arrays that the trainer re-shards onto whatever mesh it now has.
"""
from __future__ import annotations

import json
from functools import partial
from typing import Any

import jax
import numpy as np

from repro.core import metrics
from repro.core.ptlrpc import RpcError, TimeoutError_
from repro.fsio.client import FsError, LustreClient
from repro.kernels import ops as kops


# what a dead or deactivated OST can raise on the read path; any other
# error (a bug, a device or kernel failure) propagates instead of being
# retried as a parity reconstruction
_LOST = (FsError, RpcError, TimeoutError_)


# Device leaves come to the host, and parity is computed, this many bytes
# at a time, so the host holds at most two copies of the leaf being saved.
SAVE_PIECE_BYTES = 64 << 20


@partial(jax.jit, static_argnums=2)
def _device_piece(x, start, n):
    return jax.lax.dynamic_slice_in_dim(x.reshape(-1), start, n)


@metrics.spanned("ckpt.d2h")
def _host_bytes(leaf) -> bytes:
    """The leaf's bytes. A device array is copied to the host a piece at
    a time, through temporary slices on the device: `np.asarray` on the
    leaf itself would cache a host copy of the whole leaf on it for as
    long as the trainer keeps the leaf."""
    if not isinstance(leaf, jax.Array):
        return np.ascontiguousarray(leaf).tobytes()
    size = leaf.size
    n = max(1, SAVE_PIECE_BYTES // leaf.dtype.itemsize)
    return b"".join(np.asarray(_device_piece(leaf, a, min(n, size - a)))
                    for a in range(0, size, n))


def _leaf_paths(tree, prefix=()):
    """Stable (path, leaf) list without jax dependency on the hot path."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaf_paths(tree[k], prefix + (str(k),))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _leaf_paths(v, prefix + (str(i),))
    else:
        yield ".".join(prefix), tree


def _unflatten(skeleton, values: dict):
    if isinstance(skeleton, dict):
        return {k: _unflatten(v, values[k]) for k, v in skeleton.items()}
    return skeleton, values


def _quant_int8(arr: np.ndarray, block: int = 256):
    """Blockwise symmetric int8: q = round(x / s), s = absmax/127 per
    block (the error-feedback-free storage variant of adamw.compress)."""
    flat = arr.astype(np.float32).ravel()
    n = len(flat)
    pad = (-n) % block
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    blocks = flat.reshape(-1, block)
    scales = (np.abs(blocks).max(axis=1) / 127.0 + 1e-12).astype(np.float32)
    q = np.clip(np.round(blocks / scales[:, None]), -127, 127).astype(
        np.int8)
    return q.ravel()[:n + pad], scales, block


def _dequant_int8(data: bytes, entry: dict) -> np.ndarray:
    qm = entry["quant"]
    ns, blk = qm["n_scales"], qm["block"]
    scales = np.frombuffer(data[:ns * 4], np.float32)
    q = np.frombuffer(data[ns * 4:], np.int8).astype(np.float32)
    out = (q.reshape(-1, blk) * scales[:, None]).ravel()
    n = int(np.prod(entry["shape"]))
    return out[:n].astype(qm["orig_dtype"]).reshape(entry["shape"])


class CheckpointManager:
    def __init__(self, clients: list[LustreClient], base: str = "/ckpt",
                 *, stripe_count: int = 0, stripe_size: int = 1 << 20,
                 parity: bool = False, use_wbc: bool = True,
                 quantize: str | None = None):
        """`clients` = parallel writer hosts (>=1). parity=True adds an
        erasure stripe per tensor file. quantize="int8" stores float
        tensors as blockwise int8 + f32 scales (4x less wire/disk; lossy —
        meant for high-frequency intermediate checkpoints)."""
        self.clients = clients
        self.fs = clients[0]
        self.sim = self.fs.sim
        self.base = base.rstrip("/")
        self.stripe_count = stripe_count
        self.stripe_size = stripe_size
        self.parity = parity
        self.use_wbc = use_wbc
        self.quantize = quantize
        self.fs.mkdir_p(self.base)

    # -------------------------------------------------------------- save
    def _step_dir(self, step: int) -> str:
        return f"{self.base}/step_{step:08d}"

    def save(self, step: int, tree: Any, *, extra_meta: dict | None = None
             ) -> dict:
        """Write one checkpoint. Returns the manifest.

        Leaves may be device arrays: each is copied to the host a piece
        at a time as its file is written (`_host_bytes`)."""
        leaves = list(_leaf_paths(tree))
        d = self._step_dir(step)
        # overwrite semantics: a re-save of the same step (two trainers
        # resumed from one checkpoint) replaces the old content
        if self.fs.exists(d):
            for f in sorted(self.fs.readdir(d)):
                try:
                    self.fs.unlink(f"{d}/{f}")
                except FsError:
                    pass
        # metadata burst: create the step dir + files under a WBC subtree
        # lock when the MDS grants one (ch. 17)
        self.fs.mkdir_p(d)
        if self.use_wbc:
            self.fs.enable_wbc(d)
        manifest = {"step": step, "leaves": {}, **(extra_meta or {})}

        def write_leaf(w_idx: int, name: str, leaf):
            fs = self.clients[w_idx % len(self.clients)]
            qmeta = None
            if self.quantize == "int8" and leaf.dtype.kind == "f" \
                    and leaf.size >= 256:
                q, scales, blk = _quant_int8(np.asarray(leaf))
                data = scales.tobytes() + q.tobytes()
                qmeta = {"block": blk, "n_scales": len(scales),
                         "orig_dtype": str(leaf.dtype)}
            else:
                data = _host_bytes(leaf)
            fh = fs.creat(f"{d}/{name}.bin",
                          stripe_count=self.stripe_count,
                          stripe_size=self.stripe_size)
            fs.write(fh, data, gid=1 + w_idx)       # group locks (ch.10.10)
            fs.close(fh)
            entry = {"shape": list(leaf.shape), "dtype": str(leaf.dtype),
                     "bytes": len(data), "writer": w_idx % len(self.clients)}
            if qmeta:
                entry["quant"] = qmeta
            if self.parity and len(data) > 0:
                p = self._parity_for(fh, data)
                pfh = fs.creat(f"{d}/{name}.parity",
                               stripe_count=1,
                               stripe_offset=self._parity_ost(fh))
                fs.write(pfh, p, gid=1 + w_idx)
                fs.close(pfh)
                entry["parity"] = True
            return name, entry

        if self.use_wbc:
            self.fs.disable_wbc()      # flush the metadata batch first
        outs = self.sim.parallel([
            (lambda i=i, n=n, a=a: write_leaf(i, n, a))
            for i, (n, a) in enumerate(leaves)])
        for name, entry in outs:
            manifest["leaves"][name] = entry
        for fs in self.clients:
            fs.sync()
        # commit record LAST: a manifest present == checkpoint complete
        mdata = json.dumps(manifest).encode()
        fh = self.fs.creat(f"{d}/MANIFEST.json", stripe_count=1)
        self.fs.write(fh, mdata)
        self.fs.close(fh)
        self.fs.sync()
        for t in self.fs.cluster.ost_targets:       # durable commit point
            t.commit()
        self.sim.stats.count("ckpt.saved")
        return manifest

    def _parity_for(self, fh, data: bytes) -> bytes:
        """XOR parity across the file's stripe columns (Pallas kernel),
        a run of whole stripe rounds at a time: each run's parity is the
        matching run of the whole file's."""
        lsm = fh.lsm
        ssz, cnt = lsm.stripe_size, lsm.stripe_count
        if cnt < 2:
            return kops.parity_bytes([data])
        rnd = ssz * cnt
        step = max(1, SAVE_PIECE_BYTES // rnd) * rnd
        view = memoryview(data)
        out = []
        for a in range(0, len(data), step):
            piece = view[a:a + step]
            ncols = -(-len(piece) // ssz)
            rows = [b"".join(piece[j * ssz:(j + 1) * ssz]
                             for j in range(i, ncols, cnt))
                    for i in range(cnt)]
            out.append(kops.parity_bytes([r for r in rows if r]))
        return b"".join(out)

    @staticmethod
    def _parity_ost(fh) -> int:
        """Place parity on an OST not holding any data stripe if possible."""
        lsm = fh.lsm
        return (lsm.stripe_offset + lsm.stripe_count) % max(
            1, len(fh.lsm.objects) + 1)

    # ------------------------------------------------------------ restore
    def steps(self) -> list[int]:
        try:
            names = self.fs.readdir(self.base)
        except FsError:
            return []
        out = []
        for n in names:
            if n.startswith("step_"):
                s = int(n.split("_")[1])
                if self.fs.exists(f"{self.base}/{n}/MANIFEST.json"):
                    out.append(s)
        return sorted(out)

    def latest(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    def restore(self, step: int | None = None) -> tuple[dict, dict]:
        """Returns ({leaf_name: np.ndarray}, manifest). Reads leaves in
        parallel across reader clients; reconstructs stripes lost to dead
        OSTs from parity when enabled."""
        if step is None:
            step = self.latest()
        if step is None:
            raise FsError(-2, "no complete checkpoint")
        d = self._step_dir(step)
        fh = self.fs.open(f"{d}/MANIFEST.json")
        manifest = json.loads(self.fs.read(fh, 1 << 24))
        self.fs.close(fh)
        names = sorted(manifest["leaves"])

        def read_leaf(i: int, name: str):
            fs = self.clients[i % len(self.clients)]
            e = manifest["leaves"][name]
            try:
                fh = fs.open(f"{d}/{name}.bin")
                data = fs.read(fh, e["bytes"])
                fs.close(fh)
                if len(data) != e["bytes"]:
                    raise FsError(-5, "short read")
            except _LOST:
                if not e.get("parity"):
                    raise
                data = self._reconstruct(fs, d, name, e)
            if e.get("quant"):
                return name, _dequant_int8(data, e)
            return name, np.frombuffer(data, e["dtype"]).reshape(e["shape"])

        outs = self.sim.parallel([
            (lambda i=i, n=n: read_leaf(i, n))
            for i, n in enumerate(names)])
        self.sim.stats.count("ckpt.restored")
        return dict(outs), manifest

    def _reconstruct(self, fs: LustreClient, d: str, name: str,
                     e: dict) -> bytes:
        """One stripe object is gone (dead OST disk): rebuild it from the
        surviving stripes + parity (ch. 15 / Pallas reconstruct)."""
        from repro.core import lov as lov_mod
        meta = fs.lmv.getattr(fs.resolve(f"{d}/{name}.bin"), want_ea=True)
        lsm = lov_mod.StripeMd.from_ea(meta["ea"]["lov"])
        ssz, cnt = lsm.stripe_size, lsm.stripe_count
        total = e["bytes"]
        rows: list[bytes | None] = []
        missing = None
        for i, o in enumerate(lsm.objects):
            try:
                osc = fs.lov.by_uuid[o["ost"]]
                sz = lov_mod.Lov._obj_size_for(lsm, i, total)
                rows.append(osc.read(o["group"], o["oid"], 0, sz))
            except _LOST:
                if missing is not None:
                    raise FsError(-5, "more than one stripe lost")
                missing = i
                rows.append(None)
        pfh = fs.open(f"{d}/{name}.parity")
        par = fs.read(pfh, 1 << 30)
        fs.close(pfh)
        if missing is None:
            # file itself was readable after all
            rows_b = rows
        else:
            surv = [r for r in rows if r is not None]
            want = lov_mod.Lov._obj_size_for(lsm, missing, total)
            rec = kops.reconstruct_bytes(
                [r.ljust(len(par), b"\0") for r in surv],
                par, len(par))[:want]
            rows[missing] = rec
            rows_b = rows
            self.sim.stats.count("ckpt.stripe_reconstructed")
        # interleave stripe rows back into the logical byte stream
        out = bytearray(total)
        for i, row in enumerate(rows_b):
            for j in range(0, len(row), ssz):
                snum = (j // ssz) * cnt + i
                lpos = snum * ssz
                chunk = row[j:j + ssz]
                out[lpos:lpos + len(chunk)] = chunk[:max(0, total - lpos)]
        return bytes(out)

    # ----------------------------------------------------------- cleanup
    def cleanup_incomplete(self) -> list[str]:
        """Remove step dirs without a manifest (writer died mid-save)."""
        removed = []
        try:
            names = self.fs.readdir(self.base)
        except FsError:
            return removed
        for n in sorted(names):
            if not n.startswith("step_"):
                continue
            d = f"{self.base}/{n}"
            if self.fs.exists(f"{d}/MANIFEST.json"):
                continue
            for f in sorted(self.fs.readdir(d)):
                try:
                    self.fs.unlink(f"{d}/{f}")
                except FsError:
                    pass
            self.fs.rmdir(d)
            removed.append(n)
            self.sim.stats.count("ckpt.incomplete_removed")
        return removed

    def retain(self, keep: int = 3):
        """Delete old complete checkpoints beyond `keep`."""
        for s in self.steps()[:-keep]:
            d = self._step_dir(s)
            for f in sorted(self.fs.readdir(d)):
                self.fs.unlink(f"{d}/{f}")
            self.fs.rmdir(d)
