"""DLM-covered OSC clean read cache + readahead (ISSUE-4 tentpole).

Covers the acceptance criteria:
  * a sequential re-read of a cached striped file issues ZERO OST_READ
    RPCs (and, via LVB-served getattr, zero RPCs at all);
  * a 2-client write-after-read scenario proves blocking-AST
    invalidation — the reader sees the new data, never a stale cache;
  * eviction/cancel/disconnect paths invalidate too;
  * the seek-aware BRW cost model charges scattered niobuf vectors more
    than contiguous ones.
"""
import pytest

from repro.core import LustreCluster
from repro.core import dlm as D
from repro.core import ptlrpc as R
from repro.fsio import LustreClient


def mk(**kw):
    kw.setdefault("osts", 4)
    kw.setdefault("mdses", 1)
    kw.setdefault("clients", 3)
    kw.setdefault("commit_interval", 256)
    return LustreCluster(**kw)


def reads(c):
    return c.stats.counters.get("rpc.ost.read", 0)


def rpcs(c):
    """Every OST-bound RPC (read, getattr, enqueue, ...)."""
    return sum(n for k, n in c.stats.counters.items()
               if k.startswith("rpc.ost."))


# --------------------------------------------------------- osc-level cache

def test_reread_served_from_clean_cache_zero_rpcs():
    c = mk()
    osc = c.make_oscs(c.make_client_rpc(0))[0]
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"x" * 8192)
    osc.flush()
    assert osc.read(0, oid, 0, 8192) == b"x" * 8192   # promoted at flush
    base = reads(c)
    for _ in range(4):
        assert osc.read(0, oid, 0, 8192) == b"x" * 8192
        assert osc.read(0, oid, 100, 50) == b"x" * 50
    assert reads(c) == base                    # all hits, zero OST_READs
    assert c.stats.counters["osc.cache_hit"] >= 8


def test_cold_read_populates_cache():
    c = mk()
    w = c.make_oscs(c.make_client_rpc(0), writeback=False)[0]
    oid = w.create(0)["oid"]
    w.write(0, oid, 0, bytes(range(256)) * 16)         # 4 KiB
    r = c.make_oscs(c.make_client_rpc(1))[0]
    assert r.read(0, oid, 0, 4096) == bytes(range(256)) * 16
    base = reads(c)
    assert r.read(0, oid, 1024, 512) == (bytes(range(256)) * 16)[1024:1536]
    assert reads(c) == base                    # sub-range hit, no RPC
    assert c.stats.counters["osc.cache_miss"] >= 1
    assert c.stats.counters["osc.cache_hit"] >= 1


def test_blocking_ast_drops_clean_pages():
    """ISSUE-4 bugfix: revocation must invalidate CLEAN pages, not just
    flush dirty ones — without it a second client's write leaves the
    first client's cache permanently stale."""
    c = mk()
    a = c.make_oscs(c.make_client_rpc(0))[0]
    b = c.make_oscs(c.make_client_rpc(1))[0]
    oid = a.create(0)["oid"]
    a.write(0, oid, 0, b"old-old-")
    a.flush()
    assert a.read(0, oid, 0, 8) == b"old-old-"         # cached clean
    assert a.clean_bytes > 0
    b.write(0, oid, 0, b"new-new-")                    # AST revokes a's lock
    b.flush()
    assert a.clean_bytes == 0                          # pages invalidated
    assert a.read(0, oid, 0, 8) == b"new-new-"         # never stale
    assert c.stats.counters["osc.cache_invalidate"] >= 1


def test_cancel_invalidates_clean_pages():
    c = mk()
    osc = c.make_oscs(c.make_client_rpc(0))[0]
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"d" * 4096)
    osc.flush()
    assert osc.read(0, oid, 0, 4096) == b"d" * 4096
    assert osc.clean_bytes > 0
    osc.locks.cancel_all()
    assert osc.clean_bytes == 0                # cancel dropped the pages
    base = reads(c)
    assert osc.read(0, oid, 0, 4096) == b"d" * 4096
    assert reads(c) == base + 1                # re-fetched from the OST


def test_eviction_drops_locks_dirty_and_clean_state():
    """ISSUE-4 satellite: after rpc.evicted_reconnect the OSC must not
    keep locks, dirty extents, clean pages, or the grant."""
    c = LustreCluster(osts=1, mdses=1, clients=2, commit_interval=8)
    a = c.make_oscs(c.make_client_rpc(0))[0]
    b = c.make_oscs(c.make_client_rpc(1), writeback=False)[0]
    oid = a.create(0)["oid"]
    a.write(0, oid, 0, b"doomed-dirty")        # cached under a PW lock
    a.read(0, oid, 0, 4)                       # and some clean state
    assert a.dirty_bytes > 0 and a.locks.locks
    # a goes silent; b's conflicting lock evicts it server-side (§7.4)
    c.sim.faults.down_nids.add(a.rpc.nid)
    b.lock(0, oid, "PW", (0, 100))
    assert c.stats.counters["dlm.evictions"] == 1
    c.sim.faults.down_nids.discard(a.rpc.nid)  # a comes back...
    assert a.statfs()["capacity"] > 0          # -107 -> reconnect cycle
    assert c.stats.counters["rpc.evicted_reconnect"] >= 1
    assert a.dirty_bytes == 0 and a.dirty == []     # dirty data LOST
    assert a.clean_bytes == 0 and not a.locks.locks
    assert c.stats.counters["osc.evicted"] >= 1


def test_lru_budget_bounds_cache():
    c = mk(max_cached_mb=1)                    # 1 MiB budget via cluster knob
    osc = c.make_oscs(c.make_client_rpc(0))[0]
    oid = osc.create(0)["oid"]
    chunk = 256 << 10
    for i in range(8):                         # 2 MiB through a 1 MiB cache
        osc.write(0, oid, i * chunk, bytes([i]) * chunk)
        osc.flush()
    assert osc.clean_bytes <= 1 << 20
    assert c.stats.counters["osc.cache_lru_evict"] >= 1
    # unevicted tail still hits; evicted head re-fetches, both correct
    assert osc.read(0, oid, 7 * chunk, chunk) == bytes([7]) * chunk
    assert osc.read(0, oid, 0, chunk) == bytes([0]) * chunk


def test_max_cached_mb_zero_disables_cache():
    c = mk()
    osc = c.make_oscs(c.make_client_rpc(0), max_cached_mb=0)[0]
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"z" * 4096)
    osc.flush()
    base = reads(c)
    osc.read(0, oid, 0, 4096)
    osc.read(0, oid, 0, 4096)
    assert reads(c) == base + 2                # every read pays an RPC
    assert osc.clean_bytes == 0


# ----------------------------------------------------- fsio acceptance

def test_sequential_reread_of_striped_file_zero_ost_reads():
    """Acceptance: sequential re-read of a cached striped file = 0
    OST_READ RPCs (the warm path is zero OST RPCs of ANY kind: size
    checks ride the cached locks' LVBs)."""
    c = mk()
    fs = LustreClient(c).mount()
    fh = fs.creat("/seq.bin", stripe_count=4, stripe_size=1 << 18)
    data = bytes(range(256)) * 4096            # 1 MiB over 4 stripes
    fs.write(fh, data)
    fs.fsync(fh)
    chunk = 64 << 10
    out = b"".join(fs.read(fh, chunk, offset=off)
                   for off in range(0, len(data), chunk))
    assert out == data                         # cold pass populates
    base_reads, base_all = reads(c), rpcs(c)
    out = b"".join(fs.read(fh, chunk, offset=off)
                   for off in range(0, len(data), chunk))
    assert out == data
    assert reads(c) == base_reads              # ZERO OST_READ RPCs
    assert rpcs(c) == base_all                 # and zero OST RPCs at all


def test_readahead_cuts_cold_read_rpcs_4x():
    """Acceptance: readahead cuts the cold sequential-read RPC count by
    >= 4x vs readahead disabled."""
    def cold_rpcs(ra_pages):
        c = mk(readahead_pages=ra_pages)
        w = LustreClient(c, 0).mount()
        fh = w.creat("/ra.bin", stripe_count=4, stripe_size=1 << 20)
        data = b"R" * (4 << 20)
        w.write(fh, data)
        w.fsync(fh)
        r = LustreClient(c, 1).mount()         # cold client cache
        fh2 = r.open("/ra.bin")
        base = reads(c)
        chunk = 64 << 10
        out = b"".join(r.read(fh2, chunk) for _ in range(len(data) // chunk))
        assert out == data
        return reads(c) - base
    no_ra = cold_rpcs(0)
    with_ra = cold_rpcs(256)
    assert with_ra * 4 <= no_ra, (no_ra, with_ra)


def test_readahead_fans_out_one_vectored_read_per_stripe():
    """A readahead window spanning stripe objects is fetched as ONE
    vectored OST_READ per stripe object."""
    c = mk(readahead_pages=256)                # 1 MiB window
    w = LustreClient(c, 0).mount()
    fh = w.creat("/fan.bin", stripe_count=4, stripe_size=1 << 16)  # 64 KiB
    data = b"F" * (1 << 20)
    w.write(fh, data)
    w.fsync(fh)
    r = LustreClient(c, 1).mount()
    fh2 = r.open("/fan.bin")
    base = reads(c)
    r.read(fh2, 4096)                          # sequential start at 0
    # miss (<=1 RPC) + a window striped over 4 objects: the window fetch
    # costs at most one vectored OST_READ per stripe object
    assert c.stats.counters["lov.readahead"] >= 1
    assert reads(c) - base <= 1 + 4
    assert fh2.ra_pos > 4096                   # window fetched ahead
    # read the WHOLE file in 4 KiB chunks: 256 chunk reads collapse into
    # a handful of vectored window fetches (<= 4 RPCs each), everything
    # else is served from the clean cache
    while fh2.pos < len(data):
        r.read(fh2, 4096)
    assert reads(c) - base <= 32               # vs 256 without readahead
    assert c.stats.counters["osc.cache_hit"] >= 200


def test_seek_resets_readahead_window():
    c = mk(readahead_pages=16)
    fs = LustreClient(c).mount()
    fh = fs.creat("/rand.bin", stripe_count=1)
    fs.write(fh, b"r" * (1 << 20))
    fs.fsync(fh)
    fs.read(fh, 4096, offset=0)
    assert fh.ra_window > 0
    fs.read(fh, 4096, offset=512 << 10)        # seek: detector resets
    assert fh.ra_window == 0


def test_backward_seek_rescan_readahead_still_batches():
    """A backward seek must also reset the fetch horizon (ra_pos): after
    invalidation, re-scanning an already-read range has to readahead
    again, not degrade to one RPC per chunk."""
    c = mk(readahead_pages=256)
    w = LustreClient(c, 0).mount()
    fh = w.creat("/scan.bin", stripe_count=4, stripe_size=1 << 20)
    data = b"1" * (2 << 20)
    w.write(fh, data)
    w.fsync(fh)
    r = LustreClient(c, 1).mount()
    fh2 = r.open("/scan.bin")
    while fh2.pos < len(data):                 # full sequential pass
        r.read(fh2, 64 << 10)
    w.write(fh, b"2" * len(data), offset=0)    # invalidates r's cache
    w.fsync(fh)
    base = reads(c)
    out = b"".join(r.read(fh2, 64 << 10, offset=off)
                   for off in range(0, len(data), 64 << 10))
    assert out == b"2" * len(data)
    assert reads(c) - base <= 12, reads(c) - base   # batched, not 32x 1-RPC


def test_write_after_read_two_clients_never_stale():
    """Acceptance: reader caches a striped file; a second client
    overwrites it; the reader sees the new data (AST invalidation), never
    the stale cache."""
    c = mk()
    r = LustreClient(c, 0).mount()
    w = LustreClient(c, 1).mount()
    fh_w = w.creat("/shared.bin", stripe_count=4, stripe_size=1 << 16)
    v1 = b"1" * (512 << 10)
    w.write(fh_w, v1)
    w.fsync(fh_w)
    fh_r = r.open("/shared.bin")
    assert r.read(fh_r, len(v1), offset=0) == v1       # cached
    assert r.read(fh_r, len(v1), offset=0) == v1       # warm hit
    v2 = b"2" * (512 << 10)
    w.write(fh_w, v2, offset=0)                # revokes r's PR locks
    w.fsync(fh_w)
    assert r.read(fh_r, len(v2), offset=0) == v2       # sees NEW data
    # and the writer's dirty-cache variant: don't even flush
    v3 = b"3" * (512 << 10)
    w.write(fh_w, v3, offset=0)                # sits dirty under PW
    assert r.read(fh_r, len(v3), offset=0) == v3       # AST flushed + fresh
    w.close(fh_w)
    r.close(fh_r)


def test_mds_eviction_purges_dentry_cache():
    """Satellite: eviction by the MDS drops cached dentries + their
    locks (not just the replay queue)."""
    c = LustreCluster(osts=1, mdses=1, clients=1, commit_interval=8)
    fs = LustreClient(c).mount()
    fs.mkdir("/d")
    fs.creat("/d/f")
    fs.stat("/d/f")                            # populate dcache
    assert fs.dcache
    mds = c.mds_targets[0]
    mds.evicted.add(fs.rpc.uuid)               # server-side eviction
    mds.ldlm.evict_client(fs.rpc.uuid)
    # the client only learns of the eviction when it next talks to the
    # MDS (a warm stat is served from the attr/dentry caches with zero
    # RPCs since ISSUE-5) — force one RPC, then everything purges
    fs.mkdir("/d2")                            # -107 -> reconnect + purge
    assert c.stats.counters["fs.evicted_invalidate"] >= 1
    assert not fs.attr_cache
    assert fs.stat("/d/f")["type"] == "file"   # re-fetched, still correct
    assert c.stats.counters["rpc.evicted_reconnect"] >= 1


# ------------------------------------------------ covers() regression

def test_cached_cr_lock_does_not_satisfy_pr():
    """ISSUE-4 satellite: Lock.covers had a dead if/pass branch; the real
    mode-strength check must refuse CR-for-PR."""
    cr = D.Lock(1, ("ext", 0, 1), "CR", (0, 1000), "c", "n", granted=True)
    assert not cr.covers("PR", (0, 10))
    assert not cr.covers("PW", (0, 10))
    assert cr.covers("CR", (0, 10))
    assert cr.covers("NL", (0, 10))


def test_mode_strength_matches_vms_matrix():
    for held in D.MODES:
        for req in D.MODES:
            if D.mode_covers(held, req):
                # holding `held` must protect at least as much as `req`
                for other in D.MODES:
                    assert D._C[held][other] <= D._C[req][other], \
                        (held, req, other)
    assert D.mode_covers("PW", "PR") and D.mode_covers("EX", "PW")
    assert not D.mode_covers("PR", "PW") and not D.mode_covers("NL", "CR")


# ---------------------------------------------- seek-aware BRW costs

def test_scattered_niobufs_cost_more_than_contiguous():
    c = mk()
    svc = c.ost_targets[0].service
    pg = 4096
    contig = R.Request(opcode="write", body={"niobufs": [
        {"offset": i * pg, "data": b"x" * pg} for i in range(8)]})
    scattered = R.Request(opcode="write", body={"niobufs": [
        {"offset": i * 10 * pg, "data": b"x" * pg} for i in range(8)]})
    c_cost = svc.request_cost(contig)
    s_cost = svc.request_cost(scattered)
    assert s_cost > c_cost
    # 8 seeks vs 1 seek, same pages
    assert abs((s_cost - c_cost) - 7 * svc.seek_cost) < 1e-12


def test_contiguous_runs_charge_one_seek_plus_pages():
    c = mk()
    svc = c.ost_targets[0].service
    pg = 4096
    req = R.Request(opcode="read", body={"niobufs": [
        {"offset": 0, "length": pg}, {"offset": pg, "length": pg},
        {"offset": 2 * pg, "length": 2 * pg}]})
    assert abs(svc.request_cost(req)
               - (svc.cpu_cost + svc.seek_cost + 4 * svc.page_cost)) < 1e-12


def test_non_bulk_request_costs_cpu_only():
    c = mk()
    svc = c.ost_targets[0].service
    req = R.Request(opcode="getattr", body={"group": 0, "oid": 1})
    assert svc.request_cost(req) == svc.cpu_cost


def test_nrs_sees_scatter_cost():
    """End-to-end: the seek count lands in the stats the NRS/benchmarks
    read."""
    c = mk()
    osc = c.make_oscs(c.make_client_rpc(0))[0]
    oid = osc.create(0)["oid"]
    for i in range(4):
        osc.write(0, oid, i * 40960, b"s" * 4096)      # scattered runs
    osc.flush()
    assert c.stats.counters["nrs.seeks"] >= 4


# ------------------------------------------------------------- procfs

def test_cache_stats_in_procfs():
    c = mk()
    fs = LustreClient(c).mount()
    fh = fs.creat("/p.bin", stripe_count=1)
    fs.write(fh, b"p" * 8192)
    fs.fsync(fh)
    fs.read(fh, 8192, offset=0)
    fs.read(fh, 8192, offset=0)
    p = c.procfs()
    cc = p["client_cache"]
    assert cc["hits"] >= 1
    assert 0.0 <= cc["hit_rate"] <= 1.0
    assert "osc.cache_hit" in p["counters"]


# ------------------------------------ page-cache extents (no coalescing)

def _copied_under_profiler(tmp_path, fn):
    """Run fn with the JAX profiler on; return the `copied` count of the
    `osc.io` spans it recorded."""
    import jax
    from repro.core import metrics
    metrics.HOST_SPANS.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert metrics.profiling()
        fn()
    finally:
        jax.profiler.stop_trace()
    events, dropped = metrics.read_spans()
    metrics.HOST_SPANS.clear()
    assert dropped == 0
    return sum(c.get("copied", 0) for n, _, _, _, c in events
               if n == "osc.io")


def test_sequential_inserts_copy_each_byte_once(tmp_path):
    """N sequential adjacent inserts add N units to `copied`; coalescing
    each into one growing extent added 1 + 2 + ... + N."""
    c = mk()
    w = c.make_oscs(c.make_client_rpc(0), writeback=False)[0]
    oid = w.create(0)["oid"]
    unit, n = 16 << 10, 16
    data = bytes(range(256)) * (unit * n // 256)
    w.write(0, oid, 0, data)
    r = c.make_oscs(c.make_client_rpc(1))[0]
    out = []
    copied = _copied_under_profiler(tmp_path, lambda: out.extend(
        r.read(0, oid, i * unit, unit) for i in range(n)))
    assert b"".join(out) == data
    assert copied == n * unit
    base = reads(c)
    assert r.read(0, oid, 0, len(data)) == data        # joined, no RPC
    assert reads(c) == base


def test_insert_over_two_older_extents_new_data_wins():
    c = mk()
    osc = c.make_oscs(c.make_client_rpc(0))[0]
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"a" * 4096)
    osc.flush()
    osc.write(0, oid, 4096, b"b" * 4096)
    osc.flush()
    osc._clean_insert(0, oid, 2048, b"n" * 4096)
    base = reads(c)
    assert osc.read(0, oid, 0, 8192) == \
        b"a" * 2048 + b"n" * 4096 + b"b" * 2048
    assert reads(c) == base
    assert osc.clean_bytes == 8192             # trimmed, nothing doubled
    exts = osc.clean[(0, oid)]
    assert [(e.offset, e.end) for e in exts] == \
        [(0, 2048), (2048, 6144), (6144, 8192)]


def test_read_spanning_adjacent_extents_is_a_hit():
    c = mk()
    osc = c.make_oscs(c.make_client_rpc(0))[0]
    oid = osc.create(0)["oid"]
    osc.write(0, oid, 0, b"a" * 4096)
    osc.flush()
    osc.write(0, oid, 4096, b"b" * 4096)
    osc.flush()
    assert len(osc.clean[(0, oid)]) == 2       # kept as inserted
    base, hits = reads(c), c.stats.counters["osc.cache_hit"]
    assert osc.read(0, oid, 2048, 4096) == b"a" * 2048 + b"b" * 2048
    assert reads(c) == base
    assert c.stats.counters["osc.cache_hit"] == hits + 1


def test_invalidating_a_subrange_keeps_the_rest_cached():
    c = mk()
    w = c.make_oscs(c.make_client_rpc(0), writeback=False)[0]
    oid = w.create(0)["oid"]
    data = bytes(range(256)) * 256            # 64 KiB
    w.write(0, oid, 0, data)
    r = c.make_oscs(c.make_client_rpc(1))[0]
    assert r.read(0, oid, 0, len(data)) == data        # one extent
    r.write(0, oid, 16 << 10, b"w" * (16 << 10))       # supersedes 16-32K
    base = reads(c)
    assert r.read(0, oid, 0, 16 << 10) == data[:16 << 10]
    assert r.read(0, oid, 32 << 10, 32 << 10) == data[32 << 10:]
    assert reads(c) == base                    # the rest still hits
    r.flush()
    assert r.read(0, oid, 0, len(data)) == \
        data[:16 << 10] + b"w" * (16 << 10) + data[32 << 10:]
    assert reads(c) == base                    # flushed pages joined in
    r.punch(0, oid, 48 << 10)                  # drops [48K, end) only
    assert r.read(0, oid, 0, 48 << 10) == \
        data[:16 << 10] + b"w" * (16 << 10) + data[32 << 10:48 << 10]
    assert reads(c) == base


def test_lru_evicts_least_recently_used_extent_first():
    c = mk(max_cached_mb=1)
    osc = c.make_oscs(c.make_client_rpc(0))[0]
    oid = osc.create(0)["oid"]
    chunk = 256 << 10
    offs = [i * 2 * chunk for i in range(5)]   # gaps: never adjacent
    for i, off in enumerate(offs[:4]):         # A B C D fill 1 MiB
        osc.write(0, oid, off, bytes([i]) * chunk)
        osc.flush()
    assert osc.clean_bytes == 1 << 20
    base = reads(c)
    assert osc.read(0, oid, offs[0], chunk) == bytes([0]) * chunk  # A used
    osc.write(0, oid, offs[4], bytes([4]) * chunk)                 # E
    osc.flush()
    assert c.stats.counters["osc.cache_lru_evict"] == 1
    for i in (0, 2, 3, 4):                     # B went, the others stay
        assert osc.read(0, oid, offs[i], chunk) == bytes([i]) * chunk
    assert reads(c) == base
    assert osc.read(0, oid, offs[1], chunk) == bytes([1]) * chunk
    assert reads(c) == base + 1


def test_raid5_overwrite_rmw_reads_hit_the_writers_cache():
    """A raid5 half-round overwrite reads the round's other units back:
    the client that wrote them serves those reads from its clean cache
    (a parity write no longer drops the object's other units), and the
    OSTs' data and parity still match the numpy RAID-5 reference."""
    import numpy as np
    from chipbench.reference import raid5 as ref
    ssz, k, rounds = 16 << 10, 4, 4
    rng = np.random.default_rng(7)
    v1, v2 = (rng.integers(0, 256, rounds * k * ssz, dtype=np.uint8)
              for _ in range(2))

    def overwrite(same_client):
        c = LustreCluster(osts=5, mdses=1, clients=2, commit_interval=256)
        w = LustreClient(c, 0).mount()
        fh = w.creat("/f", stripe_count=k, stripe_size=ssz,
                     pattern="raid5")
        xfer = 2 * ssz                         # half a round
        for off in range(0, v1.size, xfer):
            w.write(fh, v1[off:off + xfer].tobytes(), offset=off)
        w.close(fh)
        fs = w if same_client else LustreClient(c, 1).mount()
        fh = fs.open("/f", "w")
        cnt = c.stats.counters
        h0, r0 = cnt.get("osc.cache_hit", 0), reads(c)
        for off in range(0, v2.size, xfer):
            fs.write(fh, v2[off:off + xfer].tobytes(), offset=off)
        objs = [np.frombuffer(bytes(c.target(o["ost"]).obd.objects[
            (o["group"], o["oid"])].data), np.uint8)
            for o in fh.lsm.objects]
        assert ref.compare_objects(v2, objs, ssz, k) == (0, 0)
        return cnt.get("osc.cache_hit", 0) - h0, reads(c) - r0

    warm_hits, warm_reads = overwrite(True)
    cold_hits, cold_reads = overwrite(False)
    assert warm_hits > cold_hits
    assert warm_reads < cold_reads
    assert warm_reads == 0                     # every RMW read hit
