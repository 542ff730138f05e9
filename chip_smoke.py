#!/usr/bin/env python3
"""Chip smoke test: the system's main path once, on a TPU, checked.

    python3 chip_smoke.py [--seed N]              # phases (a)-(e), one chip
    python3 chip_smoke.py --chips 4 [--seed N]    # four-chip path only

Everything runs in this one process: a chip belongs to one process at a
time. All data and weights are generated from --seed.

  (a) device gate: the first device must be a TPU. There is no CPU
      fallback.
  (b) the Pallas XOR parity kernels, compiled for the chip, against numpy.
  (c) raid5 storage path: a 64 MiB file over 4+1 OSTs, a degraded read
      with one OST dead, a rebuild onto the spare, and a read after it.
      Every read must be byte-identical to what was written.
  (d) the trainer at Qwen3-4B's full width, cut to one layer, over a
      corpus striped on the cluster, ending in a parity-coded checkpoint
      of the whole training state.
  (e) resume round trip at the smoke size, with an OST killed mid-run:
      the restore is bit-identical to the state in memory, and two
      resumed trainers train identically.

With --chips 4 the script runs only: Qwen3-4B at full width and 8 layers
under FSDP on a (4, 1) mesh, then at the smoke size one device against
(4, 1) on the first loss, and a train on (4, 1) resumed on (2, 2).

Each phase prints one line. The last line of stdout is
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Any failed check exits non-zero before that line is printed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Full-width phases: Qwen3-4B [hf:Qwen/Qwen3-4B] keeps every width; only
# depth is cut to what one chip's 16 GB (one layer) or four chips' 64 GB
# (eight layers) hold with fp32 params and Adam state.
ONE_CHIP_LAYERS = 1
FOUR_CHIP_LAYERS = 8
FULL_SEQ, FULL_BATCH = 1024, 4
STEPS = 3
RAID5_BYTES = 64 << 20
# first-step loss of one device against (4, 1): bf16 compute, so the
# reduction order may move the loss by a few bf16 ulps
LOSS_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, t0: float, **fields):
    print(f"phase {phase}: " + json.dumps(
        {"seconds": time.perf_counter() - t0, **fields}), flush=True)


def host_rss_peak_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def host_rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def device_peaks(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit is counted as a compile that took
    the time of the cache read)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


# ------------------------------------------------------------------ (b)
def phase_kernels(seed: int, shapes=((4, 262144), (3, 1000))) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as kops

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    out = {}
    for shape in shapes:
        x = rng.integers(-2**31, 2**31, size=shape, dtype=np.int32)
        want = np.bitwise_xor.reduce(x, axis=0)
        xj = jnp.asarray(x)
        got = np.asarray(kops.xor_parity(xj))
        check(np.array_equal(got, want), f"xor_parity {shape} != numpy")
        lost = shape[0] // 2
        surv = jnp.asarray(np.delete(x, lost, axis=0))
        rec = np.asarray(kops.reconstruct(surv, jnp.asarray(want)))
        check(np.array_equal(rec, x[lost]), f"reconstruct {shape} != row")
        texts = (jax.jit(kops.xor_parity).lower(xj).as_text(),
                 jax.jit(kops.reconstruct).lower(
                     surv, jnp.asarray(want)).as_text())
        check(all("tpu_custom_call" in t for t in texts),
              f"kernels at {shape} were not compiled for the TPU")
        out["x".join(map(str, shape))] = {"equal": True,
                                          "tpu_custom_call": True}
    report("b kernels", t0, **out)
    return out


# ------------------------------------------------------------------ (c)
def phase_raid5(seed: int, size: int = RAID5_BYTES,
                stripe_size: int = 1 << 20) -> dict:
    import numpy as np
    from repro.core import LustreCluster
    from repro.fsio import LustreClient

    t0 = time.perf_counter()
    data = np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    c = LustreCluster(osts=5, mdses=1, clients=3, spare_osts=1,
                      commit_interval=256)
    fs = LustreClient(c, 0).mount()
    fh = fs.creat("/r5.bin", stripe_count=4, stripe_size=stripe_size,
                  stripe_offset=0, pattern="raid5")
    fs.write(fh, data, offset=0)
    fs.close(fh)
    for t in c.ost_targets:
        t.commit()
    t_write = time.perf_counter() - t0

    def cold_read(idx: int) -> bytes:
        r = LustreClient(c, idx).mount()
        r.deactivate_ost("OST0001")
        f = r.open("/r5.bin")
        got = r.read(f, size, offset=0)
        r.close(f)
        return got

    c.fail_node("ost1")
    t1 = time.perf_counter()
    degraded = cold_read(1)
    t_degraded = time.perf_counter() - t1
    check(degraded == data, "raid5 degraded read differs from written")
    units = c.stats.counters.get("lov.reconstruct_unit", 0)
    check(units > 0, "degraded read reconstructed no unit")
    t1 = time.perf_counter()
    rep = c.lctl("rebuild", "OST0001", c.spare_uuids[0])
    t_rebuild = time.perf_counter() - t1
    check(rep["rebuilt"] >= 1, f"rebuild rebuilt nothing: {rep}")
    t1 = time.perf_counter()
    after = cold_read(2)
    t_after = time.perf_counter() - t1
    check(after == data, "raid5 read after rebuild differs from written")
    out = {"bytes": size, "identical_degraded": True,
           "identical_after_rebuild": True,
           "lov.reconstruct_unit": units,
           "rebuilt_files": rep["rebuilt"], "rebuilt_bytes": rep["bytes"],
           "write_s": t_write, "degraded_read_s": t_degraded,
           "rebuild_s": t_rebuild, "read_after_rebuild_s": t_after}
    report("c raid5", t0, **out)
    return out


# ------------------------------------------------------------------ (d)
def _trainer_cfg(model, seq, batch, *, n_steps, ckpt_every, seed,
                 dataset_seqs):
    from repro.models.config import RunConfig
    from repro.train.trainer import TrainerConfig
    return TrainerConfig(
        model=model,
        rc=RunConfig(seq_len=seq, global_batch=batch, kind="train",
                     attn_impl="ref"),
        n_steps=n_steps, ckpt_every=ckpt_every, dataset_seqs=dataset_seqs,
        n_writers=2, parity=True, seed=seed)


def phase_trainer(seed: int, meter: CompileMeter, model=None, mesh=None,
                  seq: int = FULL_SEQ, batch: int = FULL_BATCH,
                  label: str = "d trainer") -> dict:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core import LustreCluster
    from repro.train.trainer import Trainer

    t0 = time.perf_counter()
    rss0 = host_rss_bytes()
    model = model or get_config("qwen3-4b").scaled(n_layers=ONE_CHIP_LAYERS)
    cfg = _trainer_cfg(model, seq, batch, n_steps=STEPS, ckpt_every=STEPS,
                       seed=seed, dataset_seqs=16 * batch)
    c0 = meter.snapshot()
    cluster = LustreCluster(osts=4, mdses=1, clients=2, commit_interval=64)
    tr = Trainer(cluster, cfg, mesh=mesh)
    metrics = tr.run(STEPS)
    losses = [m["loss"] for m in metrics]
    check(len(losses) == STEPS and all(np.isfinite(losses)),
          f"trainer losses not finite: {losses}")
    check(tr.ckpt.steps() == [STEPS],
          f"checkpoint at step {STEPS} not complete: {tr.ckpt.steps()}")
    n_leaves = len(jax.tree.leaves(tr._state_tree()))
    names = tr.ckpt.fs.readdir(tr.ckpt._step_dir(STEPS))
    n_parity = sum(n.endswith(".parity") for n in names)
    check(n_parity == n_leaves,
          f"{n_parity} parity files for {n_leaves} leaves")
    devices = tr.mesh.devices.ravel().tolist()
    out = {"model": model.name, "n_layers": model.n_layers,
           "d_model": model.d_model, "vocab": model.vocab,
           "params": model.n_params, "mesh": dict(tr.mesh.shape),
           "losses": losses, "step_s": [m["step_s"] for m in metrics],
           "save_s": metrics[-1]["save_s"], "ckpt_leaves": n_leaves,
           "ckpt_state_bytes": sum(
               x.nbytes for x in jax.tree.leaves(tr._state_tree())),
           "peak_bytes_in_use": device_peaks(devices),
           "host_rss_start_bytes": rss0,
           "host_rss_peak_bytes": host_rss_peak_bytes(),
           **{k: v - c0[k] for k, v in meter.snapshot().items()}}
    report(label, t0, **out)
    return out


# ------------------------------------------------------------------ (e)
def phase_resume(seed: int, mesh=None, resume_mesh=None,
                 label: str = "e resume") -> dict:
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.core import LustreCluster
    from repro.train.trainer import Trainer

    t0 = time.perf_counter()
    cfg = _trainer_cfg(get_smoke_config("qwen3-4b"), 32, 4, n_steps=4,
                       ckpt_every=2, seed=seed, dataset_seqs=128)
    cluster = LustreCluster(osts=3, mdses=1, clients=2, ost_failover=True,
                            commit_interval=64)
    tr = Trainer(cluster, cfg, mesh=mesh)
    tr.run(4, fail_at={2: lambda c: c.fail_node("ost1")})
    want = jax.tree.map(np.asarray, tr._state_tree())
    got_tr = Trainer.resume(cluster, cfg, mesh=resume_mesh)
    check(got_tr.step == 4, f"resumed at step {got_tr.step}, not 4")
    got = jax.tree.map(np.asarray, got_tr._state_tree())
    flat_w = jax.tree.leaves_with_path(want)
    flat_g = jax.tree.leaves(got)
    check(len(flat_w) == len(flat_g), "restored tree has other leaves")
    for (path, a), b in zip(flat_w, flat_g):
        check(a.dtype == b.dtype and np.array_equal(a, b),
              f"restored leaf {jax.tree_util.keystr(path)} differs")
    a = Trainer.resume(cluster, cfg, mesh=resume_mesh)
    b = Trainer.resume(cluster, cfg, mesh=resume_mesh)
    la = [m["loss"] for m in a.run(2)]
    lb = [m["loss"] for m in b.run(2)]
    check(la == lb, f"resumed trainers diverge: {la} vs {lb}")
    out = {"leaves_bit_identical": len(flat_w), "resumed_losses": la,
           "ckpt.stripe_reconstructed":
               cluster.stats.counters.get("ckpt.stripe_reconstructed", 0),
           "ckpt.restored": cluster.stats.counters.get("ckpt.restored", 0)}
    report(label, t0, **out)
    return out


# ------------------------------------------------------------ four chips
def four_chip(seed: int, meter: CompileMeter, model=None) -> dict:
    """FSDP at full width on (4, 1), then its comparisons at smoke size."""
    import jax
    import numpy as np
    from repro.configs import get_config, get_smoke_config
    from repro.core import LustreCluster
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.train.trainer import Trainer

    devs = jax.devices()[:4]
    mesh41 = make_host_mesh(devices=devs)
    model = model or get_config("qwen3-4b").scaled(n_layers=FOUR_CHIP_LAYERS)
    full = phase_trainer(seed, meter, model=model, mesh=mesh41,
                         label="4a fsdp full width")
    peaks = full["peak_bytes_in_use"]
    if all(peaks):
        check(max(peaks) <= 1.25 * min(peaks),
              f"per-device peak bytes not balanced: {peaks}")

    t0 = time.perf_counter()
    cfg = _trainer_cfg(get_smoke_config("qwen3-4b"), 32, 4, n_steps=1,
                       ckpt_every=1, seed=seed, dataset_seqs=64)
    first = {}
    for name, mesh in (("1x1", make_host_mesh(devices=devs[:1])),
                       ("4x1", mesh41)):
        cluster = LustreCluster(osts=2, mdses=1, clients=2,
                                commit_interval=64)
        first[name] = Trainer(cluster, cfg, mesh=mesh).run(1)[0]["loss"]
    rel = abs(first["4x1"] - first["1x1"]) / abs(first["1x1"])
    check(rel <= LOSS_RTOL, f"first loss 1x1 vs 4x1 off by {rel}: {first}")
    report("4b one device vs (4,1)", t0, first_loss=first, rel_diff=rel,
           rtol=LOSS_RTOL)

    mesh22 = make_mesh((2, 2), ("data", "model"), devices=devs)
    elastic = phase_resume(seed, mesh=mesh41, resume_mesh=mesh22,
                           label="4c train (4,1) resume (2,2)")
    return {"full": full, "first_loss": first, "elastic": elastic}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # (a) device gate, before any work
    t0 = time.perf_counter()
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    src = os.path.join(HERE, "src")
    if dev.platform != "tpu":
        fault = f"needs a TPU, found {dev.platform!r}"
    elif device["count"] < args.chips:
        fault = f"--chips {args.chips} but {device['count']} devices"
    elif not os.path.isdir(os.path.join(src, "repro")):
        fault = f"no repro package under {src}"
    else:
        fault = None
    if fault:
        print(f"chip_smoke: {fault}", file=sys.stderr)
        return 1
    report("a device", t0, **device)
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = CompileMeter(jax)
    print(f"compile cache: {cache_dir}", flush=True)

    if args.chips == 4:
        four_chip(args.seed, meter)
        device["count"] = 4
    else:
        from repro.launch.mesh import make_host_mesh
        one = make_host_mesh(devices=jax.devices()[:1])
        print(f"cut: qwen3-4b n_layers 36 -> {ONE_CHIP_LAYERS} (a whole "
              f"period of the uniform stack); every width kept", flush=True)
        phase_kernels(args.seed)
        phase_raid5(args.seed)
        gc.collect()
        phase_trainer(args.seed, meter, mesh=one)
        gc.collect()
        phase_resume(args.seed, mesh=one, resume_mesh=one)
    print(f"compile: {json.dumps(meter.snapshot())}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
