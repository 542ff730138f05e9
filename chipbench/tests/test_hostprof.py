"""Host time grouped into the program's layers, on a synthetic profile."""
import pytest

from chipbench import hostprof

LAYERS = hostprof.load_layers()

CLIENT = ("/x/src/repro/fsio/client.py", 532, "write")
LOV = ("/x/src/repro/core/lov.py", 347, "_raid5_write")
OST = ("/x/src/repro/core/ost.py", 260, "op_write")
OPS = ("/x/src/repro/kernels/ops.py", 46, "parity_bytes")
JAX = ("/x/site-packages/jax/_src/api.py", 10, "device_put")
NP = ("~", 0, "<built-in method numpy.asarray>")
BENCH = ("/x/chipbench/drivers/ior.py", 90, "op")


def test_layer_of_takes_the_longest_pattern():
    assert hostprof.layer_of(CLIENT[0], LAYERS) == "client"
    assert hostprof.layer_of(LOV[0], LAYERS) == "client"
    assert hostprof.layer_of(OST[0], LAYERS) == "server"
    assert hostprof.layer_of(OPS[0], LAYERS) == "kernel_call"
    assert hostprof.layer_of(JAX[0], LAYERS) is None


def test_unlayered_time_goes_to_the_calling_layers():
    # numpy's asarray: 3 s called from the kernel wrapper, 1 s from the
    # OST; jax's device_put: 2 s, called from the kernel wrapper only
    stats = {
        BENCH: (1, 1, 0.5, 10.0, {}),
        CLIENT: (1, 1, 1.0, 9.5, {BENCH: (1, 1, 1.0, 9.5)}),
        LOV: (1, 1, 0.25, 8.5, {CLIENT: (1, 1, 0.25, 8.5)}),
        OST: (1, 1, 2.0, 3.0, {LOV: (1, 1, 2.0, 3.0)}),
        OPS: (1, 1, 0.75, 5.75, {LOV: (1, 1, 0.75, 5.75)}),
        JAX: (1, 1, 2.0, 2.0, {OPS: (1, 1, 2.0, 2.0)}),
        NP: (2, 2, 4.0, 4.0, {OPS: (1, 1, 3.0, 3.0),
                              OST: (1, 1, 1.0, 1.0)}),
    }
    got = hostprof.by_layer(stats, LAYERS)
    assert got == {"harness": 0.5, "client": 1.25, "server": 3.0,
                   "kernel_call": 5.75}


def test_a_recursion_of_unlayered_code_is_resolved():
    a = ("/x/lib/a.py", 1, "a")
    b = ("/x/lib/b.py", 1, "b")
    stats = {
        OST: (1, 1, 1.0, 4.0, {}),
        a: (2, 2, 1.0, 3.0, {OST: (1, 1, 0.5, 3.0), b: (1, 1, 0.5, 1.0)}),
        b: (1, 1, 2.0, 2.0, {a: (1, 1, 2.0, 2.0)}),
    }
    got = hostprof.by_layer(stats, LAYERS)
    assert got == pytest.approx({"server": 4.0})


def test_cumulative():
    stats = {OPS: (1, 1, 0.75, 5.75, {})}
    assert hostprof.cumulative(stats, "repro/kernels/ops.py",
                               "parity_bytes") == 5.75
    assert hostprof.cumulative(stats, "repro/kernels/ops.py", "x") == 0
