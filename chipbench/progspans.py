"""The program's own host spans (`repro.core.metrics`) in the traced part
of a window: self time and counts by span name, and device idle time by
the innermost program span.

The program keeps its spans only while the JAX profiler records, so its
buffer holds the traced part's spans, and those of any earlier traced
stretch of the process; a span belongs to the part when its end lies in
`Part.t0`..`t1`, both on the `perf_counter` clock as the spans are. A
span's self time is its duration less the time its direct children
cover. A program without the recorder, or whose buffer overflowed, gives
None: there is nothing sound to read.
"""
from __future__ import annotations

from chipbench import trace


def _recorded():
    try:
        from repro.core import metrics
    except ImportError:
        return None
    read = getattr(metrics, "read_spans", None)
    return read() if read is not None else None


def events(part) -> list | None:
    """[(name, start_ns, end_ns, self_ns, counts)] of the program's spans
    that end inside the part, or None."""
    recorded = _recorded()
    if recorded is None:
        return None
    evs, dropped = recorded
    if dropped or not evs:
        return None
    kids = [0] * len(evs)
    for _, a, b, parent, _ in evs:
        if parent >= 0 and b:
            kids[parent] += b - a
    lo, hi = part.t0 * 1e9, part.t1 * 1e9
    out = [(n, a, b, b - a - kids[i], c)
           for i, (n, a, b, _, c) in enumerate(evs) if b and lo <= b <= hi]
    return out or None


def summary(run) -> dict | None:
    """{name: {"n", "total_s", "self_s", "counts"}} over the traced part."""
    p = run.parts.get("trace")
    evs = events(p) if p is not None else None
    if evs is None:
        return None
    out: dict = {}
    for n, a, b, self_ns, counts in evs:
        d = out.setdefault(n, {"n": 0, "total_s": 0.0, "self_s": 0.0,
                               "counts": {}})
        d["n"] += 1
        d["total_s"] += (b - a) / 1e9
        d["self_s"] += self_ns / 1e9
        for k, v in counts.items():
            d["counts"][k] = d["counts"].get(k, 0) + v
    return out


def ms_per(run, names: tuple, key: str, unit: float):
    """Milliseconds of the named spans' self time per unit of the traced
    part's amount `key`; None where none of them ran."""
    s = summary(run)
    if s is None or not any(n in s for n in names):
        return None
    amount = run.parts["trace"].amount(key) / unit
    if amount <= 0:
        return None
    return 1e3 * sum(s[n]["self_s"] for n in names if n in s) / amount


def count_per(run, name: str, count: str, key: str):
    """The named spans' count `count` per unit of the part's amount."""
    s = summary(run)
    if s is None or name not in s:
        return None
    amount = run.parts["trace"].amount(key)
    return s[name]["counts"].get(count, 0) / amount if amount > 0 else None


def mapped(run) -> list | None:
    """The part's program spans on the device trace's clock, as
    [(name, start_ns, end_ns)]. The offset is the start of the harness's
    window span in the trace less the part's start."""
    p = run.parts.get("trace")
    if p is None or p.trace is None:
        return None
    evs = events(p)
    if evs is None:
        return None
    off = p.trace.window()[0] - round(p.t0 * 1e9)
    return [(n, a + off, b + off) for n, a, b, _, _ in evs]


def idle_by_span(run) -> dict | None:
    """Device idle seconds by the innermost program span open at the time
    ('host' where none was), within the harness's window."""
    spans = mapped(run)
    if spans is None:
        return None
    tr = run.parts["trace"].trace
    lo, hi = tr.window()
    return trace.idle_by_span(trace.Trace(tr.device,
                                          [(trace.WINDOW, lo, hi)] + spans))
