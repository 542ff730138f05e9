"""IOR ior-easy writes while a dead OST is rebuilt under them
(`drivers/ior.py`'s write phase, with one OST of the RAID-5 set dead).

Set-up writes every rank's file as `ior.py` does, and `cold_files` more
files of the same size and layout from the maintenance client (the one
after the ranks): the output of an earlier ior-easy phase. A unit is a
whole pass of the ranks over their files. At a pass's start one OST of
the set is dead and deactivated on every client; the ranks overwrite
their files in round-robin transfers, degraded until their file is
rebuilt; after every `rebuild_every_rounds` rounds of the ranks'
transfers the maintenance client rebuilds one file onto the target
(`LustreClient.rebuild_ost(dead, target, limit=1)`), which swaps that
file's layout under its writer. A pass rebuilds every file once. The
first pass rebuilds `rebuild_ost`'s objects onto the spare; each later pass
fails the OST the last pass rebuilt onto, brings back the one it left
dead, has the MDS destroy that OST's stale objects (orphan cleanup), and
rebuilds onto it: every pass does the same work and holds the same
objects.
"""
from __future__ import annotations

import numpy as np

from chipbench.drivers import ior
from chipbench.reference import raid5 as ref


def _rss_bytes() -> int:
    """The process's resident memory now (0 where /proc has none)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * 4096
    except (OSError, ValueError, IndexError):
        return 0


class Cell(ior.Cell):
    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self.n_cold = traffic["cold_files"]
        self.rebuild_every = traffic["rebuild_every_rounds"] * self.ranks
        self.rebuilt: list = []       # (pass, files rebuilt) of each call
        self.pass_rss: list = []      # resident bytes at each pass's start

    def setup(self):
        from repro.fsio.client import LustreClient
        if not hasattr(LustreClient, "revalidate_layout"):
            raise RuntimeError("this program's open handles do not follow "
                               "a layout swap: a rebuilt object would miss "
                               "its writers' writes")
        super().setup()
        self.maint = LustreClient(self.cluster, self.ranks).mount()
        lay = self.cfg["layout"]
        self.cold_buf = np.zeros((self.n_cold, self.nslots), np.int64)
        for c in range(self.n_cold):
            fh = self.maint.creat(self._cold_path(c), stripe_count=self.cnt,
                                  stripe_size=self.ssz,
                                  pattern=lay["pattern"])
            for j in range(self.nslots):
                b = self._pick(self.ranks + c, j, 0)
                self.maint.write(fh, self.bufs[b], offset=j * self.xfer)
                self.cold_buf[c, j] = b
            self.maint.close(fh)
        self.dead = self.tr["rebuild_ost"]
        self.target = self.cluster.spare_uuids[0]
        self._fail(self.dead)
        self.notes["rebuilds_per_pass"] = self.ranks + self.n_cold

    def _cold_path(self, c: int) -> str:
        return f"/ior/cold.{c:05d}"

    def _clients(self):
        return self.fs + [self.maint]

    def _fail(self, uuid: str):
        self.cluster.fail_node(self._node_of(uuid))
        for fs in self._clients():
            fs.deactivate_ost(uuid)

    def _next_pass(self):
        """Fail the OST the last pass rebuilt onto; bring back the one it
        left dead, its stale objects destroyed, as the next target."""
        back, self.dead = self.dead, self.target
        self._fail(self.dead)
        self.cluster.restart_node(self._node_of(back))
        mds = self.cluster.mds_targets[0]
        groups = {o["group"] for fh in self.fh for o in fh.lsm.objects}
        for g in groups:
            mds.orphan_cleanup({back: mds.osts[back]}, group=g)
        for fs in self._clients():
            fs.activate_ost(back)
        self.target = back
        self.pass_rss.append(_rss_bytes())

    def op(self, i: int) -> dict:
        pas = i // self.ops_per_unit
        if i % self.ops_per_unit == 0 and pas > 0:
            self._next_pass()
        out = super().op(i)
        if (i + 1) % self.rebuild_every == 0:
            rep = self.maint.rebuild_ost(self.dead, self.target, limit=1)
            self.rebuilt.append((pas, rep["swapped"]))
        return out

    def finish(self):
        self.notes["pass_rss_bytes"] = self.pass_rss

    # ------------------------------------------------------------ verify
    def _files(self):
        """(path, layout as the MDS holds it, expected bytes) of every
        file."""
        out = []
        paths = [(f"/ior/file.{r:05d}", self.slot_buf[r])
                 for r in range(self.ranks)]
        paths += [(self._cold_path(c), self.cold_buf[c])
                  for c in range(self.n_cold)]
        from repro.core import lov as lov_mod
        for path, bufs in paths:
            fid = self.maint.resolve(path)
            ea = self.maint.lmv.getattr(fid, want_ea=True)["ea"]
            out.append((path, lov_mod.StripeMd.from_ea(ea["lov"]),
                        self.pool[bufs].reshape(-1)))
        return out

    def verify(self) -> list:
        """Every file read back through a fresh mount against the bytes
        last written; every object of every file's current layout, the
        rebuilt ones among them, against the reference's layout (data
        units and parity units, each parity the XOR of its round's data
        units); and every pass's rebuilds, one for each file."""
        from repro.fsio.client import LustreClient
        reader = LustreClient(self.cluster, self.ranks).mount()
        reader.deactivate_ost(self.dead)
        read_wrong = data_wrong = parity_wrong = rebuilt_wrong = 0
        lay = self.cfg["layout"]
        for path, lsm, want in self._files():
            fh = reader.open(path)
            got = np.frombuffer(reader.read(fh, want.size, offset=0),
                                np.uint8)
            reader.close(fh)
            n = min(got.size, want.size)
            read_wrong += int(np.count_nonzero(got[:n] != want[:n])) \
                + abs(got.size - want.size)
            objs = []
            for o in lsm.objects:
                store = self.cluster.target(o["ost"]).obd.objects
                obj = store.get((o["group"], o["oid"]))
                objs.append(np.frombuffer(bytes(obj.data), np.uint8)
                            if obj is not None else np.zeros(0, np.uint8))
            dw, pw = ref.compare_objects(want, objs, self.ssz, self.cnt,
                                         lay["parity_rotation"])
            data_wrong += dw
            parity_wrong += pw
            osts = [o["ost"] for o in lsm.objects]
            if self.target in osts:        # else `rebuilds_missed` shows it
                slot = osts.index(self.target)
                expect, _ = ref.expected_objects(want, self.ssz, self.cnt)
                w, g = expect[slot].reshape(-1), objs[slot]
                m = min(g.size, w.size)
                rebuilt_wrong += int(np.count_nonzero(g[:m] != w[:m])) \
                    + abs(g.size - w.size)
        per_pass = [0] * (1 + max((p for p, _ in self.rebuilt), default=-1))
        for pas, n in self.rebuilt:
            per_pass[pas] += n
        want_n = self.ranks + self.n_cold
        short = sum(abs(n - want_n) for n in per_pass)
        self.notes["rebuilt_per_pass"] = per_pass
        return [("read_bytes_wrong", read_wrong, 0),
                ("data_bytes_wrong", data_wrong, 0),
                ("parity_bytes_wrong", parity_wrong, 0),
                ("rebuilt_bytes_wrong", rebuilt_wrong, 0),
                ("rebuilds_missed", short, 0)]
