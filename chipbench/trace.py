"""Reduction of a JAX profiler trace to device busy time, kernel time, the
device operations that took most time, and idle time by host span.

The harness emits host spans with `jax.profiler.TraceAnnotation` under
names that start with `SPAN_PREFIX`; the span `cb:window` bounds the
traced part of the window. Device operations are the events of the
"XLA Ops" line of each `/device:TPU:<n>` plane.
"""
from __future__ import annotations

import dataclasses
import re

SPAN_PREFIX = "cb:"
WINDOW = "window"

_OP = re.compile(r"^%?([^\s=]+?)(?:\.\d+)*(?:\s|=|$)")


def op_name(hlo: str) -> str:
    """Short name of a device op: '%xor_parity.1 = s32[..] custom-call(..)'
    gives 'xor_parity', 'fusion.12' gives 'fusion'."""
    m = _OP.match(hlo)
    return m.group(1) if m else hlo


@dataclasses.dataclass
class Trace:
    device: dict            # plane name -> [(op, start_ns, end_ns)]
    spans: list             # [(label, start_ns, end_ns)], harness spans

    @property
    def n_devices(self) -> int:
        return max(1, len(self.device))

    def window(self) -> tuple[int, int]:
        w = [(a, b) for lab, a, b in self.spans if lab == WINDOW]
        if not w:
            raise ValueError("trace has no window span")
        return min(a for a, _ in w), max(b for _, b in w)


def from_xplane(path) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((op_name(e.name), int(e.start_ns),
                                int(e.end_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      int(e.start_ns), int(e.end_ns)))
    return Trace(device, spans)


def _clip(intervals, lo: int, hi: int):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which an op ran on a device, averaged over the devices,
    within the window."""
    lo, hi = tr.window()
    tot = 0
    for ops in tr.device.values():
        tot += sum(b - a for a, b in union(
            _clip(((a, b) for _, a, b in ops), lo, hi)))
    return tot / tr.n_devices / 1e9


def window_s(tr: Trace) -> float:
    lo, hi = tr.window()
    return (hi - lo) / 1e9


def op_seconds(tr: Trace) -> dict[str, float]:
    """Device self seconds by op name within the window, summed over
    devices. An op that encloses others on its line (a `while` loop and
    the ops of its body) keeps only the time its children do not cover."""
    lo, hi = tr.window()
    out: dict[str, float] = {}
    for ops in tr.device.values():
        clipped = sorted(((n, max(a, lo), min(b, hi)) for n, a, b in ops
                          if min(b, hi) > max(a, lo)),
                         key=lambda e: (e[1], -e[2]))
        stack: list = []          # [name, start, end, children's time]
        for name, a, b in clipped + [(None, hi + 1, hi + 1)]:
            while stack and stack[-1][2] <= a:
                n0, a0, b0, kids = stack.pop()
                out[n0] = out.get(n0, 0.0) + (b0 - a0 - kids) / 1e9
            if name is None:
                break
            if stack and b <= stack[-1][2]:
                stack[-1][3] += b - a
            stack.append([name, a, b, 0])
    return out


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_span(tr: Trace) -> dict[str, float]:
    """Seconds in which no device ran an op, by the innermost harness span
    open on the host at the time ('host' where none was)."""
    lo, hi = tr.window()
    busy = union(_clip(((a, b) for ops in tr.device.values()
                        for _, a, b in ops), lo, hi))
    # (time, order, kind, payload): ends sort before starts at one instant
    pts = []
    for a, b in busy:
        pts += [(a, 1, "busy", 1), (b, 0, "busy", -1)]
    for i, (lab, a, b) in enumerate(tr.spans):
        if lab == WINDOW:
            continue
        pts += [(a, 1, "open", (i, lab)), (b, 0, "close", (i, lab))]
    pts.append((lo, 2, "lo", None))
    pts.sort(key=lambda p: (p[0], p[1]))
    out: dict[str, float] = {}
    stack: list = []
    n_busy = 0
    prev = None
    for t, _, kind, payload in pts:
        if prev is not None and t > prev and n_busy == 0:
            a, b = max(prev, lo), min(t, hi)
            if b > a:
                lab = stack[-1][1] if stack else "host"
                out[lab] = out.get(lab, 0.0) + (b - a) / 1e9
        prev = t
        if kind == "busy":
            n_busy += payload
        elif kind == "open":
            stack.append(payload)
        elif kind == "close" and payload in stack:
            stack.remove(payload)
    if prev is not None and prev < hi and n_busy == 0:
        lab = stack[-1][1] if stack else "host"
        out[lab] = out.get(lab, 0.0) + (hi - max(prev, lo)) / 1e9
    return out
