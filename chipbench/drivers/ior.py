"""IOR-style traffic on striped files: one file per rank, fixed-size
transfers in a round-robin closed loop over the ranks.

The mix names `op` ("write" or "read"), `transfer_bytes`, and optionally
an OST to fail before the window (`fail_ost`, deactivated on every
reader). Set-up writes every rank's file once with the same transfers, in
the same order: the fresh-file write. The window runs whole passes, a
pass being one sweep of every rank over its file (`ops_per_unit`).
Writers overwrite their files; each rank reads each pass on a fresh
mount, so its client caches start cold, as IOR's read phase on another
node than the writer (`ior -C`). The mounts of the first pass are made in
set-up.

Transfer contents come from a pool of random buffers drawn from the
seed; the benchmark keeps which buffer lies in each slot of each file, so
the expected file is known without reading the program's state.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import costs
from chipbench.reference import raid5 as ref

N_BUFFERS = 16


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg = config
        self.tr = traffic
        self.seed = seed
        lay = config["layout"]
        self.ssz = lay["stripe_size"]
        self.cnt = lay["stripe_count"]
        self.xfer = traffic["transfer_bytes"]
        self.fbytes = config["file_bytes_per_rank"]
        self.ranks = config["ranks"]
        if self.fbytes % self.xfer:
            raise ValueError("file size is not a whole number of transfers")
        self.nslots = self.fbytes // self.xfer
        self.ops_per_unit = self.ranks * self.nslots
        self.op_label = traffic["op"]
        self.returned: list = []     # (rank, slot, bytes) of every read
        self.notes: dict = {}

    # ------------------------------------------------------------ set-up
    def _buffers(self):
        rng = np.random.default_rng(self.seed)
        pool = rng.integers(0, 256, (N_BUFFERS, self.xfer), dtype=np.uint8)
        self.pool = pool
        self.bufs = [row.tobytes() for row in pool]

    def _pick(self, rank: int, slot: int, pas: int) -> int:
        # the window's passes (pas >= 1) never put back the buffer set-up
        # wrote in a slot, and consecutive passes always differ, however
        # many passes the window runs
        shift = 0 if pas == 0 else 1 + (pas - 1) % (N_BUFFERS - 1)
        return (31 * rank + 7 * slot + shift) % N_BUFFERS

    def setup(self):
        from repro.core import LustreCluster
        from repro.fsio import LustreClient
        cl = self.cfg["cluster"]
        self._client_cls = LustreClient
        self.cluster = LustreCluster(
            osts=cl["osts"], spare_osts=cl["spare_osts"], mdses=cl["mdses"],
            clients=cl["clients"], nrs_policy=cl["nrs_policy"],
            commit_interval=cl["commit_interval"])
        self._buffers()
        self.fs = [LustreClient(self.cluster, r).mount()
                   for r in range(self.ranks)]
        self.fs[0].mkdir_p("/ior")
        lay = self.cfg["layout"]
        self.fh = [self.fs[r].creat(
            f"/ior/file.{r:05d}", stripe_count=self.cnt,
            stripe_size=self.ssz, pattern=lay["pattern"])
            for r in range(self.ranks)]
        # the expected content: buffer index per (rank, slot)
        self.slot_buf = np.zeros((self.ranks, self.nslots), np.int64)
        for j in range(self.nslots):
            if j == 1:               # the first sweep loads the kernel
                t0 = time.perf_counter()
            for r in range(self.ranks):
                b = self._pick(r, j, 0)
                self.fs[r].write(self.fh[r], self.bufs[b],
                                 offset=j * self.xfer)
                self.slot_buf[r, j] = b
        # the fresh-file write rate, beside the window's overwrite rate
        if self.nslots > 1:
            self.notes["fill_MiBps"] = (
                self.ranks * (self.nslots - 1) * self.xfer / (1 << 20)
                / (time.perf_counter() - t0))
        for r in range(self.ranks):
            self.fs[r].close(self.fh[r])
        dead = self.tr.get("fail_ost")
        if dead:
            self.cluster.fail_node(self._node_of(dead))
        self.readers = {}
        if self.op_label == "read":
            # warm the degraded read path on a client no rank uses
            w = LustreClient(self.cluster, self.ranks).mount()
            if dead:
                w.deactivate_ost(dead)
            fh = w.open("/ior/file.00000")
            for j in range(min(2 * self.cnt, self.nslots)):
                w.read(fh, self.xfer, offset=j * self.xfer)
            w.close(fh)
            for r in range(self.ranks):
                self._reader(r, 0)
        else:
            self.fh = [self.fs[r].open(f"/ior/file.{r:05d}", "w")
                       for r in range(self.ranks)]

    def _node_of(self, uuid: str) -> str:
        t = self.cluster.target(uuid)
        return t.node.name

    # ------------------------------------------------------------ window
    def _reader(self, rank: int, pas: int):
        key = (rank, pas)
        if key not in self.readers:
            self.readers.pop((rank, pas - 1), None)
            fs = self._client_cls(self.cluster, rank).mount()
            if self.tr.get("fail_ost"):
                fs.deactivate_ost(self.tr["fail_ost"])
            self.readers[key] = (fs, fs.open(f"/ior/file.{rank:05d}"))
        return self.readers[key]

    def op(self, i: int) -> dict:
        r = i % self.ranks
        j = i // self.ranks
        pas, slot = divmod(j, self.nslots)
        off = slot * self.xfer
        if self.op_label == "write":
            b = self._pick(r, slot, pas + 1)
            n = self.fs[r].write(self.fh[r], self.bufs[b], offset=off)
            self.slot_buf[r, slot] = b
            return {"write_bytes": n}
        fs, fh = self._reader(r, pas)
        data = fs.read(fh, self.xfer, offset=off)
        self.returned.append((r, slot, data))
        return {"read_bytes": len(data)}

    def counters(self) -> dict:
        return dict(self.cluster.stats.counters)

    def kernel_bytes(self, before: dict, after: dict) -> dict:
        def d(k):
            return after.get(k, 0) - before.get(k, 0)
        b = costs.raid5_kernel_bytes(
            d("lov.parity_bytes") + d("lov.reconstruct_bytes"), self.cnt)
        return {"xor_parity": b} if b else {}

    def finish(self):
        pass

    # ------------------------------------------------------------ verify
    def verify(self) -> list:
        """Exact comparisons with the plain reference. A write cell reads
        every object of every file as it lies on its OST and compares each
        data unit and each parity unit with the reference's layout; a read
        cell compares every byte each read returned."""
        if self.op_label == "read":
            wrong = 0
            for r, slot, data in self.returned:
                want = self.pool[self.slot_buf[r, slot]]
                got = np.frombuffer(data, np.uint8)
                n = min(len(got), len(want))
                wrong += int(np.count_nonzero(got[:n] != want[:n]))
                wrong += abs(len(got) - len(want))
            return [("read_bytes_wrong", wrong, 0)]
        data_wrong = parity_wrong = 0
        lay = self.cfg["layout"]
        for r in range(self.ranks):
            expected = self.pool[self.slot_buf[r]].reshape(-1)
            lsm = self.fh[r].lsm
            objs = []
            for o in lsm.objects:
                store = self.cluster.target(o["ost"]).obd.objects
                obj = store.get((o["group"], o["oid"]))
                objs.append(np.frombuffer(bytes(obj.data), np.uint8)
                            if obj is not None else np.zeros(0, np.uint8))
            dw, pw = ref.compare_objects(expected, objs, self.ssz, self.cnt,
                                         lay["parity_rotation"])
            data_wrong += dw
            parity_wrong += pw
        return [("data_bytes_wrong", data_wrong, 0),
                ("parity_bytes_wrong", parity_wrong, 0)]
