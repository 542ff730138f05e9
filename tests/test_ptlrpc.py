"""ptlrpc: requests, recovery semantics (paper ch. 4.5-4.8, 29)."""
import pytest

from repro.core import LustreCluster
from repro.core import ptlrpc as R


def mk(commit_interval=8, **kw):
    c = LustreCluster(osts=1, mdses=1, clients=1,
                      commit_interval=commit_interval, **kw)
    rpc = c.make_client_rpc(0)
    osc = c.make_oscs(rpc, writeback=False)[0]
    return c, rpc, osc


def test_xids_increase_and_never_reuse():
    c, rpc, osc = mk()
    xs = [rpc.next_xid() for _ in range(100)]
    assert xs == sorted(set(xs))


def test_rpc_roundtrip_and_stats():
    c, rpc, osc = mk()
    out = osc.create(0)
    assert out["oid"] >= 2
    assert c.stats.counters["rpc.ost.create"] == 1


def test_request_timeout_advances_clock_and_recovers():
    c, rpc, osc = mk()
    oid = osc.create(0)["oid"]
    t0 = c.now
    c.sim.faults.drop_next[c.ost_targets[0].node.nid] = 1
    osc.write(0, oid, 0, b"x" * 10)
    # adaptive timeouts: a cold import waits out at least at_min (the
    # fixed DEFAULT_TIMEOUT only applies with AT disabled)
    assert c.now - t0 >= R.AT_MIN
    assert c.stats.counters["rpc.timeout"] == 1
    assert osc.read(0, oid, 0, 10) == b"x" * 10


def test_handled_requests_leave_no_payload_in_request_buffers():
    """A server's pre-posted request buffer keeps nothing once the
    service has the request: a node would otherwise hold every request
    it ever received, bulk write payloads included."""
    c, rpc, osc = mk()
    oid = osc.create(0)["oid"]
    for i in range(20):
        osc.write(0, oid, i << 16, b"x" * (1 << 16))
    node = c.ost_targets[0].node
    mds = list(node.ni.portals.values())
    assert mds and all(not me.md.buffer
                       for p in mds for me in p.match_list)
    assert osc.read(0, oid, 19 << 16, 4) == b"xxxx"


def test_reply_cache_answers_resend_of_executed_update():
    c, rpc, osc = mk()
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"A" * 4)
    c.sim.faults.drop_next[rpc.nid] = 1            # lose the reply
    osc.write(0, oid, 4, b"B" * 4)
    assert c.stats.counters["rpc.reply_cache_hit"] == 1
    # the write was NOT executed twice
    assert osc.read(0, oid, 0, 8) == b"AAAABBBB"


def test_crash_loses_uncommitted_replay_restores():
    c, rpc, osc = mk(commit_interval=1000)
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"hello")
    t = c.ost_targets[0]
    assert t.committed_transno == 0
    c.fail_node("ost0")
    c.restart_node("ost0")
    assert osc.read(0, oid, 0, 5) == b"hello"
    assert c.stats.counters["rpc.replay"] == 2     # create + write


def test_committed_state_survives_without_replay():
    c, rpc, osc = mk(commit_interval=1)            # commit every op
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"hello")
    c.fail_node("ost0")
    c.restart_node("ost0")
    assert osc.read(0, oid, 0, 5) == b"hello"
    assert c.stats.counters.get("rpc.replay", 0) == 0


def test_replay_prunes_after_commit():
    c, rpc, osc = mk(commit_interval=4)
    oid = osc.create(0)["oid"]
    for i in range(8):
        osc.write(0, oid, i, b"z")
    # everything through transno 8 committed (interval 4): list small
    assert len(osc.imp.replay_list) <= 4


def test_recovery_window_gates_new_clients():
    c = LustreCluster(osts=1, mdses=1, clients=2, commit_interval=4)
    rpc1 = c.make_client_rpc(0)
    osc1 = c.make_oscs(rpc1, writeback=False)[0]
    oid = osc1.create(0)["oid"]
    c.fail_node("ost0")
    c.restart_node("ost0")
    # client 1 reconnects (recovery completes: it's the only known client)
    assert osc1.read(0, oid, 0, 0) == b""
    assert not c.ost_targets[0].recovering


def test_vbr_no_blanket_eviction_straggler_replays_late():
    """VBR replaces the pre-VBR blanket eviction at window close: a
    straggler that misses the window is merely counted, and when it
    finally returns its replays are admitted because their pre-op
    versions still match (its objects are its own)."""
    c = LustreCluster(osts=1, mdses=1, clients=2, commit_interval=1000)
    rpc1 = c.make_client_rpc(0)
    rpc2 = c.make_client_rpc(1)
    osc1 = c.make_oscs(rpc1, writeback=False)[0]
    osc2 = c.make_oscs(rpc2, writeback=False)[0]
    osc1.create(0)
    oid2 = osc2.create(0)["oid"]
    osc2.write(0, oid2, 0, b"mine")
    c.fail_node("ost0")
    c.restart_node("ost0")
    # only client1 comes back; deadline expiry closes the window WITHOUT
    # evicting client2
    osc1.statfs()
    c.sim.clock.advance(4 * R.DEFAULT_TIMEOUT)
    osc1.statfs()
    t = c.ost_targets[0]
    assert not t.recovering
    assert c.stats.counters.get("rpc.recovery_eviction", 0) == 0
    assert c.stats.counters.get("rpc.recovery_stragglers", 0) >= 1
    assert rpc2.uuid not in t.evicted
    # delayed recovery: client2 reconnects late, replays, and its data
    # survives — the version check proves the replay still applies
    assert osc2.read(0, oid2, 0, 4) == b"mine"
    assert c.stats.counters.get("rpc.vbr_admit", 0) >= 1
    assert c.stats.counters.get("rpc.vbr_eviction", 0) == 0


def test_failover_ring_walks_nids(cluster):
    rpc = cluster.make_client_rpc(0)
    osc = cluster.make_oscs(rpc, writeback=False)[0]
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"data")
    cluster.ost_targets[0].commit()
    cluster.fail_node("ost0")
    assert osc.read(0, oid, 0, 4) == b"data"
    assert osc.imp.active_nid != "elan:ost0"


def test_wire_size_estimates():
    assert R.wire_size(b"x" * 100) == 100
    assert R.wire_size({"a": 1}) > 8
    assert R.wire_size(None) == 0
