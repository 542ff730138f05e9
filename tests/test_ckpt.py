"""Checkpointing + data pipeline over the Lustre substrate."""
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.core import LustreCluster
from repro.data import TokenDataset, TokenPipeline
from repro.fsio import LustreClient


def mk(osts=4, clients=2, parity=True, **kw):
    c = LustreCluster(osts=osts, mdses=1, clients=clients,
                      commit_interval=kw.pop("commit_interval", 32))
    writers = [LustreClient(c, i % clients).mount() for i in range(clients)]
    cm = CheckpointManager(writers, stripe_count=min(3, osts),
                           stripe_size=4096, parity=parity, **kw)
    return c, writers, cm


def tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": {"w": rng.standard_normal((32, 48)).astype(np.float32),
                  "b": rng.standard_normal(48).astype(np.float32)},
            "c": rng.integers(0, 100, 17).astype(np.int32)}


def test_save_restore_roundtrip():
    c, w, cm = mk()
    t = tree()
    cm.save(10, t)
    got, m = cm.restore()
    assert m["step"] == 10
    assert (got["a.w"] == t["a"]["w"]).all()
    assert (got["a.b"] == t["a"]["b"]).all()
    assert (got["c"] == t["c"]).all()
    assert got["c"].dtype == np.int32


def test_latest_picks_max_complete():
    c, w, cm = mk()
    cm.save(1, tree(1))
    cm.save(5, tree(5))
    cm.save(3, tree(3))
    assert cm.latest() == 5
    got, _ = cm.restore(3)
    assert (got["c"] == tree(3)["c"]).all()


def test_manifest_is_commit_record():
    """A step dir without MANIFEST (writer died mid-save) is invisible to
    restore and removed by cleanup."""
    c, w, cm = mk()
    cm.save(1, tree())
    fs = w[0]
    fs.mkdir_p("/ckpt/step_00000009")
    fh = fs.creat("/ckpt/step_00000009/partial.bin")
    fs.write(fh, b"junk" * 100)
    fs.close(fh)
    assert cm.latest() == 1
    removed = cm.cleanup_incomplete()
    assert removed == ["step_00000009"]
    assert not fs.exists("/ckpt/step_00000009")


def test_parity_reconstructs_lost_stripe():
    c, w, cm = mk()
    t = tree()
    cm.save(2, t)
    fs = w[0]
    ea = fs.lmv.getattr(fs.resolve("/ckpt/step_00000002/a.w.bin"),
                        want_ea=True)["ea"]["lov"]
    victim = ea["objects"][2]
    tgt = next(x for x in c.ost_targets if x.uuid == victim["ost"])
    tgt.obd.objects.pop((victim["group"], victim["oid"]))
    got, _ = cm.restore(2)
    assert (got["a.w"] == t["a"]["w"]).all()
    assert c.stats.counters["ckpt.stripe_reconstructed"] == 1


def _saved_files(cm, step, name):
    fs = cm.fs
    out = []
    for suffix in (".bin", ".parity"):
        fh = fs.open(f"/ckpt/step_{step:08d}/{name}{suffix}")
        out.append(fs.read(fh, 1 << 24))
        fs.close(fh)
    return out


@pytest.mark.parametrize("device", [False, True])
def test_save_in_pieces_writes_the_same_files(monkeypatch, device):
    """A leaf copied off the device and parity-coded a stripe round at a
    time gives the same data and parity files as a host leaf done in one
    piece, and still restores from parity with a stripe lost."""
    import jax.numpy as jnp
    from repro.ckpt import checkpoint as ckpt_mod
    leaf = np.random.default_rng(4).standard_normal(10_001).astype(
        np.float32)          # 40004 bytes: 3 stripe rounds and a ragged one
    c, w, cm = mk()
    cm.save(1, {"x": leaf})
    want = _saved_files(cm, 1, "x")
    monkeypatch.setattr(ckpt_mod, "SAVE_PIECE_BYTES", 4096 * 3)
    cm.save(2, {"x": jnp.asarray(leaf) if device else leaf})
    assert _saved_files(cm, 2, "x") == want
    ea = w[0].lmv.getattr(w[0].resolve("/ckpt/step_00000002/x.bin"),
                          want_ea=True)["ea"]["lov"]
    victim = ea["objects"][1]
    tgt = next(x for x in c.ost_targets if x.uuid == victim["ost"])
    tgt.obd.objects.pop((victim["group"], victim["oid"]))
    for fs_ in w:                   # read cold, not from the clean cache
        for osc in fs_.lov.oscs:
            osc.locks.cancel_all()
    got, _ = cm.restore(2)
    assert (got["x"] == leaf).all()
    assert c.stats.counters["ckpt.stripe_reconstructed"] == 1


def test_no_parity_fails_on_lost_stripe():
    c, w, cm = mk(parity=False)
    cm.save(2, tree())
    fs = w[0]
    ea = fs.lmv.getattr(fs.resolve("/ckpt/step_00000002/a.w.bin"),
                        want_ea=True)["ea"]["lov"]
    victim = ea["objects"][0]
    tgt = next(x for x in c.ost_targets if x.uuid == victim["ost"])
    tgt.obd.objects.pop((victim["group"], victim["oid"]))
    # the writers' lock-covered clean caches would (correctly!) mask the
    # lost object — drop the locks so the restore reads cold
    for fs_ in w:
        for osc in fs_.lov.oscs:
            osc.locks.cancel_all()
    with pytest.raises(Exception):
        cm.restore(2)


def test_retain_deletes_old():
    c, w, cm = mk()
    for s in (1, 2, 3, 4, 5):
        cm.save(s, {"x": np.ones(4, np.float32)})
    cm.retain(2)
    assert cm.steps() == [4, 5]


def test_checkpoint_survives_ost_crash_during_save():
    """OST crashes mid-save: replay makes the save still complete."""
    c, w, cm = mk(commit_interval=10_000)
    t = tree()
    # crash an OST partway through by hooking the clock... simplest: save,
    # crash, then verify restore works because clients replay.
    cm.save(7, t)
    c.fail_node("ost1")
    c.restart_node("ost1")
    got, _ = cm.restore(7)
    assert (got["a.w"] == t["a"]["w"]).all()


# ------------------------------------------------------------- pipeline

def test_pipeline_deterministic_and_disjoint():
    c = LustreCluster(osts=4, mdses=1, clients=1, commit_interval=64)
    fs = LustreClient(c).mount()
    ds = TokenDataset(fs, vocab=500, seq_len=32, n_seqs=128,
                      stripe_count=4).build()
    pipes = [TokenPipeline(fs, ds, dp_rank=i, dp_size=4, batch_per_rank=4)
             for i in range(4)]
    seen = []
    for p in pipes:
        idx = p.indices_for(3)
        assert (p.batch_at(3) == p.batch_at(3)).all()
        seen.append(set(idx.tolist()))
    allidx = set().union(*seen)
    assert len(allidx) == sum(len(s) for s in seen)   # disjoint shards


def test_pipeline_epoch_reshuffles():
    c = LustreCluster(osts=2, mdses=1, clients=1, commit_interval=64)
    fs = LustreClient(c).mount()
    ds = TokenDataset(fs, vocab=500, seq_len=16, n_seqs=64).build()
    p = TokenPipeline(fs, ds, dp_rank=0, dp_size=1, batch_per_rank=8)
    e0 = [tuple(p.indices_for(s)) for s in range(p.per_epoch)]
    e1 = [tuple(p.indices_for(s + p.per_epoch)) for s in range(p.per_epoch)]
    assert sorted(sum(e0, ())) == sorted(sum(e1, ()))  # same coverage
    assert e0 != e1                                    # different order


def test_pipeline_tokens_match_dataset_bytes():
    c = LustreCluster(osts=2, mdses=1, clients=1, commit_interval=64)
    fs = LustreClient(c).mount()
    ds = TokenDataset(fs, vocab=500, seq_len=16, n_seqs=64, seed=3).build()
    p = TokenPipeline(fs, ds, dp_rank=0, dp_size=1, batch_per_rank=4)
    rng = np.random.default_rng(3)
    all_tokens = rng.integers(0, 500, size=(64, 16), dtype=np.int32)
    batch = p.batch_at(0)
    idx = p.indices_for(0)
    assert (batch == all_tokens[idx]).all()
