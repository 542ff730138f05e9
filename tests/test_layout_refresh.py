"""An open handle follows a layout swap: a writer that keeps a raid5 file
open while another client rebuilds a dead OST's object onto a spare
re-reads the layout at its next write (its directory's PR lock was
revoked by the swap), so the rebuilt object receives the writes that
follow and the file survives the loss of a second OST."""
import numpy as np

from repro.core import LustreCluster
from repro.fsio import LustreClient
from repro.fsio.client import LustreClient as Client

SSZ, CNT = 4096, 4
ROUND = SSZ * CNT


def _scenario():
    c = LustreCluster(osts=5, spare_osts=1, mdses=1, clients=3,
                      commit_interval=8)
    writer, rebuilder = (LustreClient(c, i).mount() for i in (0, 1))
    rng = np.random.default_rng(7)
    data = bytearray(rng.integers(0, 256, 4 * ROUND, np.uint8).tobytes())
    fh = writer.creat("/f", stripe_count=CNT, stripe_size=SSZ,
                      pattern="raid5")
    writer.write(fh, bytes(data), offset=0)
    c.fail_node(c.target("OST0001").node.name)
    writer.deactivate_ost("OST0001")
    patch = rng.integers(0, 256, ROUND, np.uint8).tobytes()
    writer.write(fh, patch, offset=0)                 # degraded
    data[0:ROUND] = patch
    rep = rebuilder.rebuild_ost("OST0001", c.spare_uuids[0])
    assert rep["rebuilt"] == rep["swapped"] == 1
    refreshed = c.stats.counters.get("fs.layout_refresh", 0)
    # after the swap: a partial-round write on every round, so that each
    # round's rebuilt unit or parity has to take it
    for r in range(4):
        b = rng.integers(0, 256, SSZ + 100, np.uint8).tobytes()
        writer.write(fh, b, offset=r * ROUND + 50)
        data[r * ROUND + 50:r * ROUND + 50 + len(b)] = b
    # a second OST lost: its units come back only from the parity and the
    # rebuilt object
    c.fail_node(c.target("OST0002").node.name)
    reader = LustreClient(c, 2).mount()
    reader.deactivate_ost("OST0002")
    rh = reader.open("/f")
    got = reader.read(rh, len(data), offset=0)
    return c, refreshed, bytes(data), got


def test_writer_follows_the_swap():
    c, before, want, got = _scenario()
    assert before == 0
    assert c.stats.counters.get("fs.layout_refresh", 0) == 1
    assert got == want


def test_a_writer_on_the_old_layout_leaves_the_rebuilt_object_stale(
        monkeypatch):
    def keep_old(self, fh):
        fh.layout_stale = False
    monkeypatch.setattr(Client, "revalidate_layout", keep_old)
    _, _, want, got = _scenario()
    assert got != want


def test_a_lost_lock_without_a_swap_keeps_the_layout():
    c = LustreCluster(osts=5, mdses=1, clients=2, commit_interval=8)
    a, b = (LustreClient(c, i).mount() for i in (0, 1))
    fh = a.creat("/g", stripe_count=CNT, stripe_size=SSZ, pattern="raid5")
    for i in range(8):
        a.write(fh, bytes([i]) * ROUND, offset=i * ROUND)
    b.creat("/h", stripe_count=CNT, stripe_size=SSZ, pattern="raid5")
    a.write(fh, b"x" * ROUND, offset=0)   # its directory's lock was lost
    assert c.stats.counters.get("fs.layout_refresh", 0) == 0
    assert a.read(fh, ROUND, offset=0) == b"x" * ROUND


def test_other_layouts_are_not_reread():
    c = LustreCluster(osts=4, mdses=1, clients=2, commit_interval=8)
    a, b = (LustreClient(c, i).mount() for i in (0, 1))
    fh = a.creat("/g", stripe_count=2, stripe_size=SSZ)
    b.creat("/h", stripe_count=2, stripe_size=SSZ)
    assert not fh.layout_stale


def test_a_rebuild_leaves_no_pages_in_the_rebuilders_cache():
    """The rebuilt object is written without a lock, so the rebuilder
    keeps none of it cached: a rebuild after rebuild does not pile up
    pages of objects that later swaps leave behind."""
    c = LustreCluster(osts=5, spare_osts=1, mdses=1, clients=2,
                      commit_interval=8)
    writer, rebuilder = (LustreClient(c, i).mount() for i in (0, 1))
    fh = writer.creat("/f", stripe_count=CNT, stripe_size=SSZ,
                      pattern="raid5")
    writer.write(fh, bytes(range(256)) * (4 * ROUND // 256), offset=0)
    writer.close(fh)
    c.fail_node(c.target("OST0001").node.name)
    rep = rebuilder.rebuild_ost("OST0001", c.spare_uuids[0])
    assert rep["rebuilt"] == 1
    spare = rebuilder.lov.by_uuid[c.spare_uuids[0]]
    assert spare.clean_bytes == 0 and not spare.dirty
