"""Beyond-paper extensions: hedged reads, int8 checkpoints, elastic
resume across different mesh shapes, example smoke runs."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.core import LustreCluster
from repro.core import lov as lov_mod
from repro.fsio import LustreClient

REPO = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------- straggler mitigation

def test_hedged_read_beats_slow_mirror():
    c = LustreCluster(osts=2, mdses=1, clients=1, commit_interval=16)
    rpc = c.make_client_rpc(0)
    # cache off: this test measures WIRE latency of the straggler mirror
    a, b = c.make_oscs(rpc, writeback=False, max_cached_mb=0)
    r = lov_mod.Raid1(a, b)
    oid = r.create()
    r.write(oid, 0, bytes(1 << 16) * 16)            # 1 MiB mirrored
    # make mirror A a straggler: its link is busy far into the future
    slow_link = (rpc.nid, c.ost_targets[0].node.nid)
    c.network.link_busy[slow_link] = c.now + 10.0
    t0 = c.now
    data = r.read_hedged(oid, 0, 1 << 16)
    dt = c.now - t0
    assert len(data) == 1 << 16
    assert dt < 1.0                                 # did NOT wait for A
    # plain read from A would have taken >= 10 s
    t0 = c.now
    r.a.read(0, oid, 0, 1 << 16)
    assert c.now - t0 > 5.0


def test_race_returns_earliest():
    c = LustreCluster(osts=1, mdses=1, clients=1)

    def fast():
        c.sim.clock.advance(0.1)
        return "fast"

    def slow():
        c.sim.clock.advance(2.0)
        return "slow"

    idx, res = c.sim.race([slow, fast])
    assert (idx, res) == (1, "fast")
    # clock advanced by the winner only
    assert abs(c.now - 0.1) < 1e-9


# ------------------------------------------------------- int8 checkpoints

def test_quantized_checkpoint_roundtrip():
    c = LustreCluster(osts=2, mdses=1, clients=1, commit_interval=32)
    fs = [LustreClient(c).mount()]
    cm = CheckpointManager(fs, stripe_count=2, stripe_size=4096,
                           quantize="int8")
    rng = np.random.default_rng(0)
    w = (rng.standard_normal((128, 64)) * 0.02).astype(np.float32)
    ints = rng.integers(0, 100, 50).astype(np.int32)
    cm.save(1, {"w": w, "step_ids": ints})
    got, m = cm.restore(1)
    # int tensors stored exactly; float tensors within int8 block error
    assert (got["step_ids"] == ints).all()
    rel = np.abs(got["w"] - w).max() / np.abs(w).max()
    assert rel < 0.02, rel
    # compression actually happened (~4x smaller than f32)
    assert m["leaves"]["w"]["bytes"] < w.nbytes // 3


def test_quantized_vs_raw_bytes_on_wire():
    c1 = LustreCluster(osts=2, mdses=1, clients=1, commit_interval=512)
    c2 = LustreCluster(osts=2, mdses=1, clients=1, commit_interval=512)
    arr = {"w": np.random.default_rng(1).standard_normal(
        (256, 256)).astype(np.float32)}
    CheckpointManager([LustreClient(c1).mount()]).save(1, arr)
    CheckpointManager([LustreClient(c2).mount()],
                      quantize="int8").save(1, arr)
    raw = c1.stats.bytes["ost.write"]
    q = c2.stats.bytes["ost.write"]
    assert q < raw / 3


# ------------------------------------------------------- elastic resume

@pytest.mark.slow
def test_elastic_resume_across_mesh_shapes():
    """Train on a (4,2) mesh, resume on (2,4): params must match exactly
    (runs in a subprocess: device count is process-global)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.core import LustreCluster
        from repro.configs import get_smoke_config
        from repro.models.config import RunConfig
        from repro.launch.mesh import make_mesh
        from repro.train.trainer import Trainer, TrainerConfig

        cluster = LustreCluster(osts=2, mdses=1, clients=2,
                                commit_interval=64)
        cfg = TrainerConfig(
            model=get_smoke_config("qwen3-4b"),
            rc=RunConfig(seq_len=32, global_batch=8, kind="train",
                         attn_impl="ref"),
            n_steps=4, ckpt_every=2, dataset_seqs=64, n_writers=1,
            parity=False)
        mesh_a = make_mesh((4, 2), ("data", "model"))
        tr = Trainer(cluster, cfg, mesh=mesh_a)
        tr.run(4)
        want = jax.tree.map(np.asarray, tr.params)

        mesh_b = make_mesh((2, 4), ("data", "model"))   # ELASTIC
        tr2 = Trainer.resume(cluster, cfg, mesh=mesh_b)
        assert tr2.step == 4
        got = jax.tree.map(np.asarray, tr2.params)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert np.array_equal(a, b)
        # and it can keep training on the new mesh
        tr2.run(2)
        print("ELASTIC-OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        timeout=600)
    assert "ELASTIC-OK" in out.stdout, out.stderr[-2000:]


# ------------------------------------------------------- example smokes

@pytest.mark.slow
@pytest.mark.parametrize("script,expect", [
    ("quickstart.py", "virtual time elapsed"),
    ("failover_demo.py", "all six failure modes recovered"),
])
def test_examples_run(script, expect):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert expect in out.stdout
