"""Arithmetic the metric readers share. A reader that finds nothing to
read returns None and the metric is left out of the line; a share of a
peak is never reported as 0 for want of a reading."""
from __future__ import annotations

from chipbench import hostprof, trace

MiB = float(1 << 20)
GiB = float(1 << 30)


def rate(run, key: str, unit: float):
    p = run.parts.get("window")
    if p is None or not p.ops or p.seconds <= 0:
        return None
    return p.amount(key) / unit / p.seconds


def layer_ms_per_mib(run, layer: str, key: str):
    """Host milliseconds of one layer per MiB moved, in the profiled part."""
    p = run.parts.get("host")
    if p is None or p.layer_s is None or layer not in p.layer_s:
        return None
    mib = p.amount(key) / MiB
    return p.layer_s[layer] * 1e3 / mib if mib > 0 else None


def kernel_roofline_pct(run, kernel: str):
    """Least time the chip's HBM bandwidth allows for the bytes the kernel
    needs, over the kernel's device time, in the traced part."""
    p = run.parts.get("trace")
    if p is None or p.trace is None or run.peaks is None:
        return None
    nbytes = p.kernel_bytes.get(kernel)
    t = trace.op_seconds(p.trace).get(kernel)
    if not nbytes or not t:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t


def device_idle_pct(run):
    p = run.parts.get("trace")
    if p is None or p.trace is None or not p.trace.device:
        return None
    w = trace.window_s(p.trace)
    return 100.0 * (1.0 - trace.busy_s(p.trace) / w) if w > 0 else None


def cumulative_ms_per(run, suffix: str, func: str, key: str, unit: float):
    p = run.parts.get("host")
    if p is None or p.stats is None:
        return None
    n = p.amount(key) / unit
    t = hostprof.cumulative(p.stats, suffix, func)
    return t * 1e3 / n if n > 0 and t > 0 else None
