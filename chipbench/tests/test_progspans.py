"""The program's own spans read by the benchmark: self times and counts
over the traced part, the mapping onto the device trace's clock, idle
time by the innermost program span, nothing read from a program without
the recorder or with a full buffer, every reader of them reporting on
each cell it lists, and every cell run untraced doing no span work, driven
at a tiny size on the CPU."""
import jax
import pytest

from chipbench import harness, progspans, registry, trace
from chipbench.tests import tiny

MS = 1_000_000
READERS = [f"{m}.{kind}" for m in (
    "client_self_ms_per_MiB", "serve_self_ms_per_MiB",
    "kernel_pack_ms_per_MiB", "kernel_transfer_ms_per_MiB",
    "cache_copy_per_byte") for kind in ("write", "read")] + [
    "d2h_ms_per_GiB", "batch_wait_ms_per_step"]
T0 = 1000 * MS          # the part's start on the perf_counter clock


def _events():
    # perf_counter ns: fs.io 1005-1090 ms holds osc.io 1020-1060 (holding
    # rpc.serve 1030-1040) and kernel.parity 1070-1085 (holding kernel.put
    # 1075-1080); one span ends before the part, one after it
    return [("fs.io", T0 + 5 * MS, T0 + 90 * MS, -1, {"bytes": 2 << 20}),
            ("osc.io", T0 + 20 * MS, T0 + 60 * MS, 0, {"copied": 3 << 20}),
            ("rpc.serve", T0 + 30 * MS, T0 + 40 * MS, 1, {}),
            ("kernel.parity", T0 + 70 * MS, T0 + 85 * MS, 0, {}),
            ("kernel.put", T0 + 75 * MS, T0 + 80 * MS, 3, {}),
            ("fs.io", T0 - 50 * MS, T0 - 10 * MS, -1, {"bytes": 99}),
            ("fs.io", T0 + 95 * MS, T0 + 150 * MS, -1, {"bytes": 99})]


def _run(recorded_trace=True):
    # on the trace's clock the window runs 500-600 ms; device ops at
    # 506-508 (fs.io's own time), 535-537 (rpc.serve), 576-578 (kernel.put)
    tr = trace.Trace(
        device={"/device:TPU:0": [("fusion", 506 * MS, 508 * MS),
                                  ("xor_parity", 535 * MS, 537 * MS),
                                  ("xor_parity", 576 * MS, 578 * MS)]},
        spans=[("window", 500 * MS, 600 * MS)])
    part = harness.Part(T0 / 1e9, (T0 + 100 * MS) / 1e9,
                        [(1.0, 1.09, {"write_bytes": 2 << 20})],
                        trace=tr if recorded_trace else None)
    return harness.Run("w", {}, {}, None, 1.0, {"trace": part})


@pytest.fixture
def recorded(monkeypatch):
    """Hand the readers (events, dropped) in place of the program's."""
    def use(events, dropped=0):
        monkeypatch.setattr(progspans, "_recorded", lambda: (events, dropped))
    return use


def test_self_time_and_counts_by_name(recorded):
    recorded(_events())
    s = progspans.summary(_run())
    assert set(s) == {"fs.io", "osc.io", "rpc.serve", "kernel.parity",
                      "kernel.put"}
    got = {n: d["self_s"] for n, d in s.items()}
    assert got == pytest.approx({"fs.io": 0.030, "osc.io": 0.030,
                                 "rpc.serve": 0.010, "kernel.parity": 0.010,
                                 "kernel.put": 0.005})
    assert s["fs.io"]["total_s"] == pytest.approx(0.085)
    assert s["fs.io"]["n"] == 1
    assert s["fs.io"]["counts"] == {"bytes": 2 << 20}
    assert s["osc.io"]["counts"] == {"copied": 3 << 20}


def test_spans_recorded_under_the_profiler(tmp_path, monkeypatch, recorded):
    # recorded by the program's own recorder, on a fake clock
    from repro.core import metrics
    ts = iter([T0 + 5 * MS, T0 + 20 * MS, T0 + 60 * MS, T0 + 90 * MS])
    monkeypatch.setattr(metrics, "_now", lambda: next(ts))
    rec = metrics.HostSpans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("fs.io"):
            rec.add("bytes", 2 << 20)
            with rec.span("osc.io"):
                rec.add("copied", 3 << 20)
    finally:
        jax.profiler.stop_trace()
    recorded(*rec.read())
    s = progspans.summary(_run())
    assert {n: d["self_s"] for n, d in s.items()} == pytest.approx(
        {"fs.io": 0.045, "osc.io": 0.040})
    assert s["osc.io"]["counts"] == {"copied": 3 << 20}


def test_idle_by_the_innermost_program_span(recorded):
    recorded(_events())
    got = progspans.idle_by_span(_run())
    # 500-505 and 590-600 no span; fs.io 505-506, 508-520, 560-570,
    # 585-590; osc.io 520-530, 540-560; rpc.serve 530-535, 537-540;
    # kernel.parity 570-575, 580-585; kernel.put 575-576, 578-580
    assert got == pytest.approx({"host": 0.015, "fs.io": 0.028,
                                 "osc.io": 0.030, "rpc.serve": 0.008,
                                 "kernel.parity": 0.010,
                                 "kernel.put": 0.003})


def test_mapped_onto_the_trace_clock(recorded):
    recorded(_events())
    spans = progspans.mapped(_run())
    assert spans[:2] == [("fs.io", 505 * MS, 590 * MS),
                         ("osc.io", 520 * MS, 560 * MS)]
    assert progspans.mapped(_run(recorded_trace=False)) is None


def test_nothing_read_from_a_full_buffer_or_no_recorder(monkeypatch,
                                                        recorded):
    run = _run()
    recorded(_events(), 1)
    assert progspans.summary(run) is None
    assert progspans.idle_by_span(run) is None
    recorded([])
    assert progspans.summary(run) is None
    monkeypatch.undo()
    from repro.core import metrics
    monkeypatch.delattr(metrics, "read_spans")
    assert progspans.summary(run) is None
    # each reader of the program's spans then reports nothing
    for name in READERS:
        assert registry.reader(name)(run) is None, name


@pytest.mark.parametrize("workload", sorted(tiny.OVERRIDES))
def test_each_reader_reports_on_its_cells(workload):
    from repro.core import metrics
    metrics.HOST_SPANS.clear()
    r = tiny.run(workload, trace=True)
    assert r["correct"], r["checks"]
    bench = registry.load_benchmark()
    want = [m["name"] for m in registry.metrics_for(bench, workload,
                                                    "per_layer")
            if m["name"] in READERS]
    assert want
    for name in want:
        assert r["metrics"][name]["value"] > 0, name


@pytest.mark.parametrize("workload", sorted(tiny.OVERRIDES))
def test_an_untraced_run_records_nothing(workload, monkeypatch):
    # with the profiler off no span site opens a span, adds a count (so
    # computes none), reads the clock or touches the profiler
    from repro.core import metrics

    def boom(*a, **kw):
        raise AssertionError("the off path did work")

    metrics.HOST_SPANS.clear()
    for name in ("add", "_Span", "_now", "_TraceMe"):
        monkeypatch.setattr(metrics, name, boom)
    r = tiny.run(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert metrics.read_spans() == ([], 0)
