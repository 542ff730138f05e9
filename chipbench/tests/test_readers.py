"""Readers and window arithmetic on hand-made runs: the step's share of
the peak over the device's busy time, latency percentiles over every
operation, and windows that end on whole units of work."""
import time

import pytest

from chipbench import harness, registry, trace

MS = 1_000_000


def _run(parts, config=None, peaks=None):
    return harness.Run("w", config or {}, {}, peaks, 1.0, parts)


def test_mfu_is_model_flops_over_device_busy_time():
    bench = registry.load_benchmark()
    cfg = registry.config(bench, "qwen3-4b-l5")
    # two steps of 4096 tokens; the device busy 0.5 s of a 1 s window
    tr = trace.Trace(device={"/device:TPU:0": [("fusion", 0, 250 * MS),
                                               ("fusion", 500 * MS,
                                                750 * MS)]},
                     spans=[("window", 0, 1000 * MS)])
    part = harness.Part(0.0, 1.0, [(0.0, 0.5, {"tokens": 4096}),
                                   (0.5, 1.0, {"tokens": 4096})], trace=tr)
    flops = registry.arch(cfg).train_flops_per_token(cfg, 1024) * 8192
    got = registry.reader("mfu")(_run({"trace": part}, cfg,
                                      {"bf16_flops": 197e12}))
    assert got == pytest.approx(100 * flops / 0.5 / 197e12)
    # no trace, or no busy time: nothing to read, never 0
    assert registry.reader("mfu")(_run({"trace": harness.Part(
        0.0, 1.0, part.ops)}, cfg, {"bf16_flops": 197e12})) is None


def test_mfu_is_per_chip():
    bench = registry.load_benchmark()
    cfg = registry.config(bench, "qwen3-4b-l5")
    peaks = {"bf16_flops": 197e12}
    busy = [("fusion", 0, 500 * MS)]
    window = [("window", 0, 1000 * MS)]

    def mfu(n_devices, tokens):
        tr = trace.Trace(device={f"/device:TPU:{k}": busy
                                 for k in range(n_devices)}, spans=window)
        part = harness.Part(0.0, 1.0, [(0.0, 1.0, {"tokens": tokens})],
                            trace=tr)
        return registry.reader("mfu")(_run({"trace": part}, cfg, peaks))

    # four devices busy alike doing 4x the work of one read the same share
    assert mfu(4, 8192) == pytest.approx(mfu(1, 2048))
    assert mfu(4, 8192) == pytest.approx(mfu(1, 8192) / 4)


def test_collective_ms_per_step_reads_collectives_per_device_and_step():
    read = registry.reader("collective_ms_per_step")
    ops = [("fusion", 0, 300 * MS), ("all-gather-start", 300 * MS,
                                     310 * MS),
           ("all-gather-done", 310 * MS, 330 * MS),
           ("reduce-scatter", 400 * MS, 440 * MS),
           ("all-reduce", 500 * MS, 510 * MS),
           ("all-to-all", 600 * MS, 606 * MS),
           ("collective-permute-done", 700 * MS, 704 * MS),
           ("copy-done", 800 * MS, 900 * MS)]
    tr = trace.Trace(device={f"/device:TPU:{k}": ops for k in range(4)},
                     spans=[("window", 0, 1000 * MS)])
    part = harness.Part(0.0, 1.0, [(0.0, 0.5, {}), (0.5, 1.0, {})],
                        trace=tr)
    # 90 ms of collectives a device over 2 steps; copies are not exchange
    assert read(_run({"trace": part})) == pytest.approx(45.0)
    # one device has no exchange to read
    one = harness.Part(0.0, 1.0, part.ops, trace=trace.Trace(
        device={"/device:TPU:0": ops}, spans=tr.spans))
    assert read(_run({"trace": one})) is None


def test_op_notes_give_latency_percentiles_of_every_operation():
    ops = [(0.0, k / 1000, {}) for k in range(1, 101)]     # 1..100 ms
    notes = harness.op_notes({"window": harness.Part(0.0, 1.0, ops)})
    assert notes["op_ms"]["p95"] == pytest.approx(95.95)
    assert notes["op_ms"]["p99"] == pytest.approx(99.99)
    assert harness.op_notes({}) == {}


class _UnitCell:
    op_label = "op"
    ops_per_unit = 5

    def op(self, i):
        time.sleep(0.004)
        return {"n": 1}


def test_window_runs_whole_units():
    part = harness._window(_UnitCell(), 0.01, 0)
    assert len(part.ops) >= 5 and len(part.ops) % 5 == 0
    # a window that starts on a unit's boundary ends on one
    part = harness._window(_UnitCell(), 0.01, 10)
    assert (10 + len(part.ops)) % 5 == 0


def test_op_notes_name_the_slowest_operations():
    ops = [(float(k), k + 0.001, {"data_s": 0.0}) for k in range(10)]
    ops[7] = (7.0, 9.5, {"data_s": 2.0})
    notes = harness.op_notes({"window": harness.Part(0.0, 10.0, ops)})
    assert notes["slowest"][0] == {"at_s": 7.0, "ms": pytest.approx(2500),
                                   "data_s": 2.0}
    assert notes["op_ms"]["max"] == pytest.approx(2500)
