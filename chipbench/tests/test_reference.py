"""The plain references against hand-made layouts, and against the
program at a small size."""
import numpy as np

from chipbench.reference import raid5, stripes


def test_raid5_left_symmetric_layout():
    ssz, k = 2, 4
    data = np.arange(3 * k * ssz, dtype=np.uint8)          # 3 rounds
    objs, is_parity = raid5.expected_objects(data, ssz, k)
    # round 0: parity on object 4, round 1 on 3, round 2 on 2
    assert is_parity[:, 0].tolist() == [False] * 4 + [True]
    assert is_parity[:, 1].tolist() == [False] * 3 + [True, False]
    assert is_parity[:, 2].tolist() == [False, False, True, False, False]
    units = data.reshape(3, k, ssz)
    assert (objs[4, 0] == np.bitwise_xor.reduce(units[0])).all()
    # round 1: data units 0-2 on objects 0-2, unit 3 past the parity
    assert (objs[4, 1] == units[1, 3]).all()
    assert (objs[2, 2] == np.bitwise_xor.reduce(units[2])).all()


def test_raid5_compare_counts_data_and_parity_apart():
    ssz, k = 4, 4
    data = np.random.default_rng(0).integers(0, 256, 2 * k * ssz,
                                             dtype=np.uint8)
    objs, is_parity = raid5.expected_objects(data, ssz, k)
    flat = [o.reshape(-1).copy() for o in objs]
    assert raid5.compare_objects(data, flat, ssz, k) == (0, 0)
    flat[4][1] ^= 1                  # round 0's parity unit
    flat[0][ssz] ^= 1                # round 1's data unit 0
    assert raid5.compare_objects(data, flat, ssz, k) == (1, 1)
    flat[1] = flat[1][:ssz]          # object 1 lost round 1's unit
    assert raid5.compare_objects(data, flat, ssz, k) == (1 + ssz, 1)


def test_raid5_reference_matches_the_program():
    from repro.core import LustreCluster
    from repro.fsio import LustreClient
    ssz, k = 4096, 4
    data = np.random.default_rng(1).integers(0, 256, 3 * k * ssz,
                                             dtype=np.uint8)
    c = LustreCluster(osts=5, mdses=1, clients=1)
    fs = LustreClient(c, 0).mount()
    fh = fs.creat("/f", stripe_count=k, stripe_size=ssz, pattern="raid5")
    fs.write(fh, data.tobytes(), offset=0)
    got = [np.frombuffer(bytes(c.target(o["ost"]).obd.objects[
        (o["group"], o["oid"])].data), np.uint8) for o in fh.lsm.objects]
    assert raid5.compare_objects(data, got, ssz, k) == (0, 0)


def test_stripes_and_column_parity():
    data = np.arange(10, dtype=np.uint8)
    objs = stripes.objects(data, 2, 3)
    assert [o.tolist() for o in objs] == [[0, 1, 6, 7], [2, 3, 8, 9],
                                          [4, 5]]
    par = stripes.column_parity(data, 2, 3)
    assert par.tolist() == [0 ^ 2 ^ 4, 1 ^ 3 ^ 5, 6 ^ 8, 7 ^ 9]
    assert stripes.count_wrong(np.array([1, 2], np.uint8),
                               np.array([1, 3, 4], np.uint8)) == 2
