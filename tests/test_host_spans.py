"""Wall-clock host spans (`core.metrics.HostSpans`): nothing is recorded,
and no clock is read, while the JAX profiler is off; under the profiler
nested spans are recorded with their parents and counts, and land in the
profiler's own trace; a full buffer counts what it drops."""
import glob
import os

import jax
import pytest

from repro.core import metrics


@pytest.fixture
def clock(monkeypatch):
    """A fake `perf_counter_ns` that steps through the given times."""
    def set_times(*ts):
        it = iter(ts)
        monkeypatch.setattr(metrics, "_now", lambda: next(it))
    return set_times


@pytest.fixture
def profiling(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        jax.profiler.stop_trace()


def _nested(rec):
    with rec.span("outer"):
        rec.add("bytes", 10)
        rec.add("bytes", 5)
        with rec.span("inner"):
            rec.add("copied", 3)
        assert rec.spanned("leaf")(lambda x: x + 1)(6) == 7


def test_profiler_off_records_nothing_and_reads_no_clock(monkeypatch):
    calls = []

    def off():
        calls.append(1)
        return False

    def boom(*a):
        raise AssertionError("the off path touched the clock or profiler")

    monkeypatch.setattr(metrics, "profiling", off)
    monkeypatch.setattr(metrics, "_now", boom)
    monkeypatch.setattr(metrics, "_TraceMe", boom)
    monkeypatch.setattr(metrics, "_Span", boom)
    rec = metrics.HostSpans()
    _nested(rec)
    assert rec.read() == ([], 0)
    assert rec.span("a") is rec.span("b")
    # one profiler check per span and nothing else
    assert len(calls) == 3 + 2


def test_profiler_is_off_outside_a_trace():
    assert not metrics.profiling()
    assert metrics.span("x") is metrics.span("y")


def test_nested_spans_under_the_profiler(clock, profiling):
    clock(0, 10, 30, 40, 45, 100)
    rec = metrics.HostSpans()
    _nested(rec)
    events, dropped = rec.read()
    assert dropped == 0
    assert events == [("outer", 0, 100, -1, {"bytes": 15}),
                      ("inner", 10, 30, 0, {"copied": 3}),
                      ("leaf", 40, 45, 0, {})]


def test_spans_land_in_the_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        _nested(metrics.HostSpans())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"lustre/outer", "lustre/inner", "lustre/leaf"} <= names


def test_a_full_buffer_counts_what_it_drops(profiling):
    rec = metrics.HostSpans(capacity=2)
    _nested(rec)
    with rec.span("late"):
        rec.add("bytes", 1)
    events, dropped = rec.read()
    assert [e[0] for e in events] == ["outer", "inner"]
    assert dropped == 2
    rec.clear()
    assert rec.read() == ([], 0)


def test_a_span_still_open_reads_end_zero(profiling):
    rec = metrics.HostSpans()
    with rec.span("open"):
        (name, _, end, parent, _), = rec.read()[0]
        assert (name, end, parent) == ("open", 0, -1)
        with pytest.raises(RuntimeError):
            rec.clear()
