"""LOV striping + RAID1 (paper ch. 10, 15, 20)."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # bare env: sampled fallback
    from _hyposhim import given, settings, strategies as st

from repro.core import LustreCluster
from repro.core import lov as LV


def mk(osts=4, policy="round_robin"):
    c = LustreCluster(osts=osts, mdses=1, clients=1, commit_interval=32)
    rpc = c.make_client_rpc(0)
    lov = c.make_lov(rpc, policy=policy)
    return c, lov


def test_chunks_mapping_round_trip():
    lsm = LV.StripeMd(stripe_size=100, stripe_count=3, stripe_offset=0,
                      objects=[])
    runs = LV._chunks(lsm, 0, 1000)
    # every logical byte covered exactly once
    covered = sorted((lpos, lpos + ln) for _, _, ln, lpos in runs)
    pos = 0
    for a, b in covered:
        assert a == pos
        pos = b
    assert pos == 1000
    # stripe index round-robins
    assert [r[0] for r in runs[:4]] == [0, 1, 2, 0]


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 4), st.integers(16, 257),
       st.lists(st.tuples(st.integers(0, 2000),
                          st.binary(min_size=1, max_size=513)),
                min_size=1, max_size=8))
def test_striped_write_read_random_extents(cnt, ssz, writes):
    """Property: arbitrary overlapping striped writes == a flat buffer."""
    c, lov = mk()
    lsm = lov.create(stripe_count=cnt, stripe_size=ssz)
    shadow = bytearray()
    for off, data in writes:
        lov.write(lsm, off, data)
        if off + len(data) > len(shadow):
            shadow.extend(b"\0" * (off + len(data) - len(shadow)))
        shadow[off:off + len(data)] = data
    lov.flush()
    assert lov.getattr(lsm)["size"] == len(shadow)
    assert lov.read(lsm, 0, len(shadow)) == bytes(shadow)
    # random sub-extent
    if len(shadow) > 3:
        a, b = len(shadow) // 3, 2 * len(shadow) // 3
        assert lov.read(lsm, a, b - a) == bytes(shadow[a:b])


def test_logical_size_formula():
    lsm = LV.StripeMd(stripe_size=10, stripe_count=3, stripe_offset=0,
                      objects=[])
    # obj0 has 2 full stripes (20B): last byte at logical ((1)*3+0)*10+9=39
    assert LV.logical_size(lsm, [20, 0, 0]) == 40
    assert LV.logical_size(lsm, [10, 5, 0]) == 15
    assert LV.logical_size(lsm, [0, 0, 0]) == 0


def test_punch_truncates_per_object():
    c, lov = mk()
    lsm = lov.create(stripe_count=4, stripe_size=16)
    lov.write(lsm, 0, bytes(range(256)))
    lov.flush()
    lov.punch(lsm, 100)
    assert lov.getattr(lsm)["size"] == 100
    assert lov.read(lsm, 0, 100) == bytes(range(100))


def test_parallel_stripes_overlap_in_virtual_time():
    """N stripes on N OSTs must take ~1/N the time of 1 stripe on 1 OST."""
    c1, lov1 = mk(osts=1)
    c4, lov4 = mk(osts=4)
    data = bytes(1024) * 512                     # 512 KiB
    lsm1 = lov1.create(stripe_count=1, stripe_size=1 << 16)
    t0 = c1.now
    lov1.write(lsm1, 0, data)
    lov1.oscs[0].flush()
    t1 = c1.now - t0
    lsm4 = lov4.create(stripe_count=4, stripe_size=1 << 16)
    t0 = c4.now
    lov4.write(lsm4, 0, data)
    lov4.flush()
    t4 = c4.now - t0
    assert t4 < t1 / 2                           # real parallel speedup


def test_free_space_policy_prefers_empty_ost():
    c, lov = mk(policy="free_space")
    # fill OST0 substantially
    big = lov.create(stripe_count=1, stripe_offset=0)
    lov.write(big, 0, b"x" * (1 << 20))
    lov.flush()
    lsm = lov.create(stripe_count=1)
    assert lsm.stripe_offset != 0


def test_stripe_offset_pins_allocation():
    c, lov = mk()
    lsm = lov.create(stripe_count=2, stripe_offset=2)
    assert lsm.objects[0]["ost"] == "OST0002"
    assert lsm.objects[1]["ost"] == "OST0003"


def test_raid1_mirror_write_and_failover_read():
    c = LustreCluster(osts=2, mdses=1, clients=1, commit_interval=4)
    rpc = c.make_client_rpc(0)
    a, b = c.make_oscs(rpc, writeback=False)
    r = LV.Raid1(a, b)
    oid = r.create()
    r.write(oid, 0, b"mirrored")
    for t in c.ost_targets:
        t.commit()
    c.fail_node("ost0")
    assert r.read(oid, 0, 8) == b"mirrored"
    assert c.stats.counters["raid1.failover_read"] == 1


def test_raid1_degraded_write_and_resync():
    c = LustreCluster(osts=2, mdses=1, clients=1, commit_interval=4)
    rpc = c.make_client_rpc(0)
    a, b = c.make_oscs(rpc, writeback=False)
    r = LV.Raid1(a, b)
    oid = r.create()
    r.write(oid, 0, b"00000000")
    for t in c.ost_targets:
        t.commit()
    c.fail_node("ost1")
    r.write(oid, 0, b"11111111")              # degraded: only mirror A
    assert c.stats.counters["raid1.degraded_write"] == 1
    c.restart_node("ost1")
    assert r.resync() == 1
    assert b.read(0, oid, 0, 8) == b"11111111"


# ------------------------------------------------- ISSUE-1 edge cases

def test_chunks_zero_length_emits_no_runs():
    lsm = LV.StripeMd(stripe_size=100, stripe_count=3, stripe_offset=0,
                      objects=[])
    assert LV._chunks(lsm, 0, 0) == []
    assert LV._chunks(lsm, 250, 0) == []
    assert LV._chunks(lsm, 10, -5) == []      # defensive: negative length


def test_chunks_boundary_end_has_no_empty_run():
    lsm = LV.StripeMd(stripe_size=100, stripe_count=3, stripe_offset=0,
                      objects=[])
    for off, ln in ((0, 100), (50, 50), (0, 300), (100, 200), (299, 1)):
        runs = LV._chunks(lsm, off, ln)
        assert all(r[2] > 0 for r in runs), (off, ln, runs)
        assert sum(r[2] for r in runs) == ln


def test_chunks_single_stripe_runs_merge():
    """stripe_count=1: object-contiguous runs coalesce into one niobuf."""
    lsm = LV.StripeMd(stripe_size=100, stripe_count=1, stripe_offset=0,
                      objects=[])
    assert LV._chunks(lsm, 0, 250) == [(0, 0, 250, 0)]


def test_chunks_degenerate_geometry():
    bad = LV.StripeMd(stripe_size=0, stripe_count=0, stripe_offset=0,
                      objects=[])
    assert LV._chunks(bad, 0, 100) == []      # no divide-by-zero


def test_logical_size_exact_boundary():
    lsm = LV.StripeMd(stripe_size=100, stripe_count=3, stripe_offset=0,
                      objects=[])
    # object 0 holding exactly 2 full stripes -> logical bytes 0-99+300-399
    assert LV.logical_size(lsm, [200, 0, 0]) == 400
    assert LV.logical_size(lsm, [100, 100, 100]) == 300
    assert LV.logical_size(lsm, []) == 0
    # stray object sizes beyond stripe_count are ignored
    assert LV.logical_size(lsm, [0, 0, 0, 500]) == 0


def test_zero_length_write_read_end_to_end():
    c, lov = mk()
    lsm = lov.create(stripe_count=2, stripe_size=4096)
    assert lov.write(lsm, 0, b"") == 0
    assert lov.read(lsm, 0, 0) == b""
    assert lov.getattr(lsm)["size"] == 0


def test_boundary_write_then_read_round_trip():
    c, lov = mk()
    lsm = lov.create(stripe_count=2, stripe_size=4096)
    data = bytes(range(256)) * 32             # exactly 2 stripes
    assert lov.write(lsm, 0, data) == len(data)
    lov.flush()
    assert lov.getattr(lsm)["size"] == len(data)
    assert lov.read(lsm, 0, len(data)) == data


# ------------------------------------------------- ISSUE-8: raid5 / SNS

from repro.core import ptlrpc as R  # noqa: E402


def mk5(osts=3, spares=0, clients=2):
    c = LustreCluster(osts=osts, mdses=1, clients=clients,
                      commit_interval=32, spare_osts=spares)
    rpc = c.make_client_rpc(0)
    lov = c.make_lov(rpc)
    return c, lov


def _r5_payload(n, seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 256, n, dtype=np.uint8).tobytes()  # non-zero


def test_r5_parity_rotation_geometry():
    lsm = LV.StripeMd(stripe_size=10, stripe_count=2, stripe_offset=0,
                      objects=[], pattern="raid5")
    # n=3 slots; the parity slot walks right-to-left one slot per round
    assert [LV._r5_parity_slot(lsm, r) for r in range(6)] == \
        [2, 1, 0, 2, 1, 0]
    # in every round the data units occupy exactly the non-parity slots
    for r in range(6):
        p = LV._r5_parity_slot(lsm, r)
        slots = [LV._r5_slot(lsm, r, i) for i in range(2)]
        assert sorted(slots + [p]) == [0, 1, 2]


def test_r5_logical_size_witnesses():
    lsm = LV.StripeMd(stripe_size=10, stripe_count=2, stripe_offset=0,
                      objects=[], pattern="raid5")
    # 25 logical bytes: slot sizes are [15, 15, 10] (parity unit length
    # mirrors data unit 0's extent in each round)
    assert LV._r5_logical_size(lsm, [15, 15, 10]) == 25
    # a parity-only witness still pins the size (unit 0's extent)
    assert LV._r5_logical_size(lsm, [0, 0, 10]) == 10
    assert LV._r5_logical_size(lsm, [0, 0, 0]) == 0
    assert LV._r5_logical_size(lsm, [None, 15, 10]) == 25  # dead slot


def test_raid5_round_trip_odd_size_and_rmw():
    c, lov = mk5()
    lsm = lov.create(stripe_count=2, stripe_size=512, stripe_offset=0,
                     pattern="raid5")
    assert lsm.pattern == "raid5" and len(lsm.objects) == 3
    data = _r5_payload(5037)                  # ragged tail unit
    lov.write(lsm, 0, data)
    assert lov.read(lsm, 0, len(data)) == data
    assert lov.getattr(lsm)["size"] == len(data)
    # read-modify-write strictly inside one unit + spanning a round
    patch = b"\xaa" * 700
    lov.write(lsm, 300, patch)
    want = data[:300] + patch + data[1000:]
    assert lov.read(lsm, 0, len(want)) == want
    assert lov.getattr(lsm)["size"] == len(want)


def test_raid5_ea_round_trip_preserves_pattern():
    c, lov = mk5()
    lsm = lov.create(stripe_count=2, stripe_size=256, pattern="raid5")
    back = LV.StripeMd.from_ea(lsm.to_ea())
    assert back.pattern == "raid5"
    assert back.objects == lsm.objects
    # pre-raid5 EAs (no pattern key) still decode as raid0
    ea = lsm.to_ea()
    ea.pop("pattern", None)
    assert LV.StripeMd.from_ea(ea).pattern == "raid0"


def test_raid5_degraded_read_is_byte_identical():
    c, lov = mk5()
    lsm = lov.create(stripe_count=2, stripe_size=512, stripe_offset=0,
                     pattern="raid5")
    data = _r5_payload(5037)
    lov.write(lsm, 0, data)
    for t in c.ost_targets:
        t.commit()
    dead = lsm.objects[1]["ost"]
    c.fail_node("ost" + str(int(dead[3:])))
    # a COLD client must reconstruct from surviving stripes + parity
    # (the writer's own clean cache would serve the bytes without RPCs)
    cold = c.make_lov(c.make_client_rpc(1))
    assert cold.read(lsm, 0, len(data)) == data
    assert c.stats.counters["lov.degraded_read"] >= 1
    assert c.stats.counters["lov.reconstruct_unit"] >= 1
    # size survives the dead slot too
    assert cold.getattr(lsm)["size"] == len(data)


def test_raid5_degraded_write_and_parity_update():
    c, lov = mk5()
    lsm = lov.create(stripe_count=2, stripe_size=256, stripe_offset=0,
                     pattern="raid5")
    data = _r5_payload(2048, seed=3)
    lov.write(lsm, 0, data)
    for t in c.ost_targets:
        t.commit()
    dead = lsm.objects[0]["ost"]
    c.fail_node("ost" + str(int(dead[3:])))
    patch = _r5_payload(512, seed=4)
    lov.write(lsm, 0, patch)                  # slot 0 dead: parity absorbs
    assert c.stats.counters["lov.degraded_write"] >= 1
    want = patch + data[512:]
    cold = c.make_lov(c.make_client_rpc(1))
    assert cold.read(lsm, 0, len(want)) == want


def test_raid5_second_failure_is_an_error_not_garbage():
    c, lov = mk5(osts=4)
    lsm = lov.create(stripe_count=3, stripe_size=256, stripe_offset=0,
                     pattern="raid5")
    lov.write(lsm, 0, _r5_payload(3000))
    c.fail_node("ost0")
    c.fail_node("ost1")
    with pytest.raises(R.RpcError):
        c.make_lov(c.make_client_rpc(1)).read(lsm, 0, 3000)


def test_raid5_rebuild_onto_spare_and_layout_swap():
    c, lov = mk5(spares=1)
    lsm = lov.create(stripe_count=2, stripe_size=256, stripe_offset=0,
                     pattern="raid5")
    data = _r5_payload(3333, seed=7)
    lov.write(lsm, 0, data)
    for t in c.ost_targets:
        t.commit()
    dead = lsm.objects[1]["ost"]
    c.fail_node("ost" + str(int(dead[3:])))
    spare_uuid = c.spare_uuids[0]
    new = lov.rebuild_object(lsm, dead, lov.by_uuid[spare_uuid])
    assert new.objects[1]["ost"] == spare_uuid
    assert [o["ost"] for o in new.objects[::2]] == \
        [o["ost"] for o in lsm.objects[::2]]  # live slots untouched
    assert c.stats.counters["lov.rebuild_object"] == 1
    assert c.stats.counters["lov.rebuild_bytes"] > 0
    # the rebuilt layout serves reads with the dead OST still down
    cold = c.make_lov(c.make_client_rpc(1))
    assert cold.read(new, 0, len(data)) == data
    # and now survives a SECOND (different) OST failing
    other = new.objects[0]["ost"]
    c.fail_node("ost" + str(int(other[3:])))
    cold2 = c.make_lov(c.make_client_rpc(1))
    assert cold2.read(new, 0, len(data)) == data


def test_raid5_punch_recomputes_tail_parity():
    c, lov = mk5()
    lsm = lov.create(stripe_count=2, stripe_size=256, stripe_offset=0,
                     pattern="raid5")
    data = _r5_payload(2048, seed=9)
    lov.write(lsm, 0, data)
    lov.punch(lsm, 700)                       # mid-unit truncate
    assert lov.getattr(lsm)["size"] == 700
    for t in c.ost_targets:
        t.commit()
    # parity of the truncated tail round must cover the new content:
    # fail a data OST and reconstruct through the truncation point
    dead = lsm.objects[0]["ost"]
    c.fail_node("ost" + str(int(dead[3:])))
    cold = c.make_lov(c.make_client_rpc(1))
    assert cold.read(lsm, 0, 700) == data[:700]


# --------------------------------------- ISSUE-8: RAID1 stale-data fixes

def _mk_raid1():
    c = LustreCluster(osts=2, mdses=1, clients=2, commit_interval=4)
    rpc = c.make_client_rpc(0)
    a, b = c.make_oscs(rpc, writeback=False)
    r = LV.Raid1(a, b)
    oid = r.create()
    return c, r, a, b, oid


def test_raid1_resync_primary_side_stale():
    """Regression (ISSUE-8 satellite 1): when the PRIMARY missed the
    write, resync must copy b->a — the old primary-first read replayed
    a's stale bytes over the up-to-date secondary."""
    c, r, a, b, oid = _mk_raid1()
    r.write(oid, 0, b"00000000")
    for t in c.ost_targets:
        t.commit()
    c.fail_node("ost0")                       # primary down
    r.write(oid, 0, b"11111111")              # only mirror B took it
    assert c.stats.counters["raid1.degraded_write"] == 1
    assert r.dirty_log[-1][3] == "a"          # the STALE side is recorded
    c.restart_node("ost0")
    assert r.resync() == 1
    assert a.read(0, oid, 0, 8) == b"11111111"   # healed, not clobbered
    assert b.read(0, oid, 0, 8) == b"11111111"


def test_raid1_read_heals_stale_primary_before_serving():
    c, r, a, b, oid = _mk_raid1()
    r.write(oid, 0, b"00000000")
    for t in c.ost_targets:
        t.commit()
    c.fail_node("ost0")
    r.write(oid, 0, b"11111111")
    c.restart_node("ost0")
    assert r.read(oid, 0, 8) == b"11111111"   # not a's stale zeros
    assert c.stats.counters["raid1.heal_on_read"] == 1
    assert not r.dirty_log
    assert a.read(0, oid, 0, 8) == b"11111111"


def test_raid1_failover_read_never_serves_stale_secondary():
    """Regression (satellite 2): secondary missed a write (dropped
    OST_WRITE), then the primary dies — failover must NOT hand out the
    secondary's stale bytes; -5 beats silently wrong data."""
    c, r, a, b, oid = _mk_raid1()
    r.write(oid, 0, b"fresh000")
    for t in c.ost_targets:
        t.commit()
    b_nid = c.ost_targets[1].node.nid
    c.sim.faults.drop_next[b_nid] += 1000     # OST_WRITE (+ resends) lost
    r.write(oid, 0, b"fresh111")
    c.sim.faults.drop_next[b_nid] = 0
    assert r.dirty_log[-1][3] == "b"
    c.fail_node("ost0")                       # up-to-date mirror dies
    with pytest.raises(R.RpcError):
        r.read(oid, 0, 8)
    assert c.stats.counters["raid1.stale_read_avoided"] >= 1
    c.restart_node("ost0")
    assert r.read(oid, 0, 8) == b"fresh111"   # served from the good side
    assert r.resync() == 1                    # and b can heal now
    assert b.read(0, oid, 0, 8) == b"fresh111"


def test_raid1_hedged_read_uses_loser_result_no_reissue():
    """Regression (satellite 4): when the race winner FAILED, the old
    code re-issued a full read() — a third RPC and a second chance to
    hit the slow path. The loser already ran; its bytes are used as-is."""
    c, r, a, b, oid = _mk_raid1()
    r.write(oid, 0, b"hedgedat")
    for t in c.ost_targets:
        t.commit()
    # cold reader client: mirror A administratively dead (fails fast,
    # wins the race with an error), mirror B must serve over the wire
    rpc2 = c.make_client_rpc(1)
    a2, b2 = c.make_oscs(rpc2, writeback=False)
    r2 = LV.Raid1(a2, b2)
    a2.set_active(False)
    before = c.stats.counters.get("rpc.ost.read", 0)
    assert r2.read_hedged(oid, 0, 8) == b"hedgedat"
    assert c.stats.counters["raid1.hedge_loser_used"] == 1
    assert c.stats.counters.get("rpc.ost.read", 0) - before == 1


def test_raid1_hedged_read_takes_dirty_aware_path():
    c, r, a, b, oid = _mk_raid1()
    r.write(oid, 0, b"00000000")
    for t in c.ost_targets:
        t.commit()
    c.fail_node("ost1")
    r.write(oid, 0, b"22222222")              # b is stale now
    c.restart_node("ost1")
    assert r.read_hedged(oid, 0, 8) == b"22222222"   # never b's zeros
    assert r.resync() == 1
    assert b.read(0, oid, 0, 8) == b"22222222"


# ------------------------- raid5: degraded size kept under the PR locks

from repro.fsio import LustreClient  # noqa: E402

SSZ5, CNT5 = 512, 2
TAIL = 3 * SSZ5 * CNT5 + SSZ5 + 300    # round 3: unit 0 full, unit 1 part


def _deg_counts(c):
    got = c.stats.counters
    return (got.get("lov.degraded_size_rebuilt", 0),
            got.get("lov.degraded_size_cached", 0))


def _degraded_file(clients=3):
    """A raid5 file whose tail lies in the dead slot: unit 1 of round 3
    holds the last 300 bytes, with unit 0 full, so a change inside the
    dead unit leaves every survivor's size as it was. The writer's locks
    are gone (a first reader revoked them); returns (cluster, data, dead
    OST uuid, mount), the mount making a client with the OST inactive."""
    c = LustreCluster(osts=3, mdses=1, clients=clients, commit_interval=32,
                      spare_osts=1)
    w = LustreClient(c, 0).mount()
    fh = w.creat("/f", stripe_count=CNT5, stripe_size=SSZ5, pattern="raid5")
    data = _r5_payload(TAIL, seed=11)
    w.write(fh, data, offset=0)
    w.close(fh)
    dead = fh.lsm.objects[LV._r5_slot(fh.lsm, 3, 1)]["ost"]
    c.fail_node(c.target(dead).node.name)

    def mount(i):
        fs = LustreClient(c, i).mount()
        fs.deactivate_ost(dead)
        return fs

    first = mount(0)
    assert first.read(first.open("/f"), TAIL, offset=0) == data
    return c, data, dead, mount


def test_raid5_degraded_reader_rebuilds_the_tail_once_per_lock_grant():
    c, data, _, mount = _degraded_file()
    for grant in range(2):                # each mount takes its own locks
        r = mount(1 + grant)
        rh = r.open("/f")
        base = _deg_counts(c)
        units = c.stats.counters.get("lov.reconstruct_unit", 0)
        got = b"".join(r.read(rh, SSZ5) for _ in range(TAIL // SSZ5 + 1))
        assert got == data
        reads = TAIL // SSZ5 + 1
        rebuilt, cached = (a - b for a, b in zip(_deg_counts(c), base))
        assert (rebuilt, cached) == (1, reads - 1)
        # the size checks rebuilt one unit; the reads rebuilt the dead
        # slot's data units, once each
        dead_data = sum(LV._r5_slot(rh.lsm, rr, i) == LV._r5_slot(
            rh.lsm, 3, 1) for rr in range(4) for i in range(CNT5))
        assert c.stats.counters["lov.reconstruct_unit"] - units == \
            1 + dead_data


def _other_extends(w, fh, data):
    more = _r5_payload(100, seed=12)
    w.write(fh, more, offset=len(data))   # still inside the dead unit
    return data + more


def _other_overwrites(w, fh, data):
    start = 3 * SSZ5 * CNT5
    new = _r5_payload(len(data) - start, seed=13)
    w.write(fh, new, offset=start)
    return data[:start] + new


def _other_punches(w, fh, data):
    w.truncate("/f", len(data) - 200)     # cut inside the dead unit
    return data[:-200]


def _other_punches_a_round(w, fh, data):
    cut = 2 * SSZ5 * CNT5 + 100           # cuts the survivors too
    w.truncate("/f", cut)
    return data[:cut]


@pytest.mark.parametrize("change", [_other_extends, _other_overwrites,
                                    _other_punches, _other_punches_a_round])
def test_raid5_degraded_size_follows_another_clients_change(change):
    c, data, _, mount = _degraded_file()
    r = mount(1)
    rh = r.open("/f")
    assert r.read(rh, TAIL, offset=0) == data
    assert r.lov.getattr_locked(rh.lsm)["size"] == TAIL
    assert _deg_counts(c)[1] >= 1         # the reader holds a kept size
    w = mount(2)
    want = change(w, w.open("/f", "w"), data)
    assert r.lov.getattr_locked(rh.lsm)["size"] == len(want)
    assert r.read(rh, 2 * TAIL, offset=0) == want


def _own_extends(r, fh, data):
    more = _r5_payload(100, seed=14)
    r.write(fh, more, offset=len(data))
    return data + more


def _own_punches(r, fh, data):
    r.truncate("/f", len(data) - 200)
    return data[:-200]


@pytest.mark.parametrize("change", [_own_extends, _own_punches])
def test_raid5_degraded_size_follows_the_readers_own_change(change):
    # a client's own PW locks leave its PR locks held: only the LOV's
    # own drop of the kept size makes the change visible
    c, data, _, mount = _degraded_file()
    r = mount(1)
    rh = r.open("/f", "w")
    assert r.read(rh, TAIL, offset=0) == data
    assert r.lov.getattr_locked(rh.lsm)["size"] == TAIL
    want = change(r, rh, data)
    rebuilt = _deg_counts(c)[0]
    assert r.lov.getattr_locked(rh.lsm)["size"] == len(want)
    assert _deg_counts(c)[0] == rebuilt + 1
    assert r.read(rh, 2 * TAIL, offset=0) == want


def test_raid5_degraded_size_after_rebuild_and_layout_swap():
    c, data, dead, mount = _degraded_file()
    r = mount(1)
    rh = r.open("/f")
    assert r.read(rh, TAIL, offset=0) == data
    old = rh.lsm
    # this LOV rebuilds the file itself: its kept size goes with the
    # old layout, and the new layout (no dead slot) sizes from witnesses
    new = r.lov.rebuild_object(old, dead, r.lov.by_uuid[c.spare_uuids[0]])
    assert LV._r5_file(old) not in r.lov._r5_sizes
    assert r.lov.getattr_locked(new)["size"] == TAIL
    assert r.lov.read(new, 0, TAIL) == data
    # another client's rebuild and swap: the reader's handle follows it
    c2, data2, dead2, mount2 = _degraded_file(clients=4)
    r2 = mount2(1)
    rh2 = r2.open("/f")
    assert r2.read(rh2, TAIL, offset=0) == data2
    rep = mount2(2).rebuild_ost(dead2, c2.spare_uuids[0])
    assert rep["swapped"] == 1
    more = _r5_payload(100, seed=15)
    w = LustreClient(c2, 3).mount()
    w.write(w.open("/f", "w"), more, offset=TAIL)
    assert r2.read(rh2, 2 * TAIL, offset=0) == data2 + more
    assert r2.lov.getattr_locked(rh2.lsm)["size"] == TAIL + 100


def _unlocked(r, lsm):
    return r.lov.getattr(lsm)["size"]


def _glimpsed(r, lsm):
    # a writer holds PW locks on every survivor: each size check is
    # answered by glimpses, under no lock of the reader's
    return r.lov.getattr_locked(lsm)["size"]


@pytest.mark.parametrize("size_of", [_unlocked, _glimpsed])
def test_raid5_degraded_size_without_held_locks_rebuilds_every_call(size_of):
    c, data, _, mount = _degraded_file()
    w = mount(1)
    fh = w.open("/f", "w")
    w.write(fh, data[:SSZ5 * CNT5 * 4], offset=0)   # PW on every survivor
    r = mount(2)
    lsm = r.open("/f").lsm
    for _ in range(3):
        units = c.stats.counters.get("lov.reconstruct_unit", 0)
        assert size_of(r, lsm) == TAIL
        assert c.stats.counters["lov.reconstruct_unit"] == units + 1
    assert _deg_counts(c)[1] == 0
    assert not r.lov._r5_sizes
