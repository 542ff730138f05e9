"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

With `trace` off the window is measured whole and the cell's end-to-end
metrics are reported. With `trace` on, the first half of the window runs
under the JAX profiler (device busy time, kernel time, idle time by host
span) and the second half under cProfile (host time by program layer), so
that neither tool's host cost is read by the other; the cell's per-layer
metrics are reported.

A cell whose operations come in units (`ops_per_unit`, such as one pass of
every rank over its file) runs whole units: its window ends with the first
unit that ends after the deadline.
"""
from __future__ import annotations

import copy
import cProfile
import dataclasses
import gc
import glob
import os
import pstats
import resource
import shutil
import statistics
import sys
import tempfile
import time
import traceback

from chipbench import device as dev
from chipbench import hostprof, patches, registry
from chipbench import trace as tr_mod

CACHE_DIR = registry.ROOT / ".jax_cache"


@dataclasses.dataclass
class Part:
    """One stretch of the window: its operations and what was read."""
    t0: float
    t1: float
    ops: list                    # [(start, end, {amount: n})]
    failed: int = 0
    kernel_bytes: dict = dataclasses.field(default_factory=dict)
    stats: dict | None = None    # pstats of the host-profiled part
    layer_s: dict | None = None  # host seconds by layer
    trace: object | None = None  # trace.Trace of the traced part

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def amount(self, key: str) -> float:
        return sum(a.get(key, 0) for _, _, a in self.ops)


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    workload: str
    config: dict
    traffic: dict
    peaks: dict | None
    setup_s: float
    parts: dict


def merge(base: dict, over: dict | None) -> dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def _window(cell, seconds: float, i0: int, annotate: bool = False) -> Part:
    import jax
    ops, failed = [], 0
    label = tr_mod.SPAN_PREFIX + cell.op_label
    unit = getattr(cell, "ops_per_unit", 1)
    i = i0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline or i % unit:
        a = time.perf_counter()
        try:
            if annotate:
                with jax.profiler.TraceAnnotation(label):
                    amt = cell.op(i)
            else:
                amt = cell.op(i)
        except Exception:
            log(traceback.format_exc())
            failed += 1
            break
        ops.append((a, time.perf_counter(), amt))
        i += 1
    return Part(t0, time.perf_counter(), ops, failed)


def _measured(cell, fn) -> Part:
    """Run one part of the window and count the kernel work it caused
    from the program's counters."""
    c0 = cell.counters()
    part = fn()
    part.kernel_bytes = cell.kernel_bytes(c0, cell.counters())
    return part


def _traced(cell, seconds: float, i0: int) -> Part:
    import jax
    layers = hostprof.load_layers()
    out = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        with patches.spans(layers["spans"], tr_mod.SPAN_PREFIX):
            jax.profiler.start_trace(out, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(
                        tr_mod.SPAN_PREFIX + tr_mod.WINDOW):
                    part = _window(cell, seconds, i0, annotate=True)
            finally:
                jax.profiler.stop_trace()
        files = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                          recursive=True)
        part.trace = tr_mod.from_xplane(files[0]) if files else None
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return part


def _host_profiled(cell, seconds: float, i0: int) -> Part:
    prof = cProfile.Profile()
    prof.enable()
    try:
        part = _window(cell, seconds, i0)
    finally:
        prof.disable()
    part.stats = pstats.Stats(prof).stats
    part.layer_s = hostprof.by_layer(part.stats, hostprof.load_layers())
    return part


class GcClock:
    """Seconds the garbage collector held the process, and its collections
    of the oldest generation, while installed in `gc.callbacks`."""

    def __init__(self):
        self.seconds, self.full, self._t = 0.0, 0, None

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self.full += info["generation"] == 2
            self._t = None


def op_notes(parts: dict) -> dict:
    """Latency quantiles of every operation in the window, in ms, and the
    slowest three with their place in the window: what a stall looks like
    from the host."""
    ops = [(s, e, a) for p in parts.values() for s, e, a in p.ops]
    if not ops:
        return {}
    lat = [1e3 * (e - s) for s, e, _ in ops]
    q = statistics.quantiles(lat, n=100) if len(lat) > 1 else lat * 99
    t0 = min(s for s, _, _ in ops)
    slow = sorted(range(len(ops)), key=lambda k: -lat[k])[:3]
    return {"op_ms": {"p50": q[49], "p95": q[94], "p99": q[98],
                      "max": max(lat)},
            "slowest": [{"at_s": ops[k][0] - t0, "ms": lat[k],
                         **{key: v for key, v in ops[k][2].items()
                            if key.endswith("_s")}} for k in slow]}


def host_memory() -> dict:
    """GiB the host has available and swapped out, from /proc/meminfo
    (empty where there is none): memory pressure behind a stall."""
    out = {}
    try:
        with open("/proc/meminfo") as f:
            kb = {k: int(v.split()[0]) for k, v in
                  (line.split(":", 1) for line in f)}
    except (OSError, ValueError):
        return out
    if "MemAvailable" in kb:
        out["available_GiB"] = kb["MemAvailable"] / (1 << 20)
    if "SwapTotal" in kb and "SwapFree" in kb:
        out["swapped_GiB"] = (kb["SwapTotal"] - kb["SwapFree"]) / (1 << 20)
    return out


def enable_compile_cache(jax) -> str:
    """JAX_COMPILATION_CACHE_DIR where set, else the fixed `.jax_cache` in
    the checkout; every program is cached, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float | None = None, gate: bool = True,
             overrides: dict | None = None, plant: dict | None = None,
             bench: dict | None = None) -> dict:
    """Run one cell and return its result line. `gate=False` (tests only)
    skips the look for a chip and leaves peaks out; `overrides` replaces
    keys of the configuration and the traffic mix (tests at tiny sizes);
    `plant` replaces program functions for the window (a control or a
    fault, see `faults`), and for set-up too where the cell checks work
    that set-up does."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = bench or registry.load_benchmark()
    wl = registry.workload(bench, workload)
    config = merge(registry.config(bench, wl["config"]),
                   (overrides or {}).get("config"))
    traffic = merge(registry.traffic(wl["traffic"]),
                    (overrides or {}).get("traffic"))
    cell_cls = registry.driver(traffic["kind"]).Cell

    import jax
    devices = jax.devices()
    if gate:
        device = dev.gate(devices, wl["chips"])
        peaks = dev.peaks(device["kind"])
        log(f"compile cache: {enable_compile_cache(jax)}")
    else:
        d0 = devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": wl["chips"]}
        peaks = None
    devices = devices[:wl["chips"]]
    meter = dev.CompileMeter(jax)

    cell = cell_cls(config, traffic, seed)
    cell.chips = wl["chips"]
    # a cell whose checked work runs in set-up has a planted fault there too
    setup_plant = plant if getattr(cell, "checked_in_setup", False) else None
    with patches.replaced(setup_plant or {}):
        cell.setup()
    # what set-up built is never garbage: keep the collector off it
    gc.collect()
    gc.freeze()
    compile_setup = meter.snapshot()
    setup_s = time.perf_counter() - t_process
    log(f"setup_s {setup_s:.3f} compile {compile_setup}")

    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    try:
        with patches.replaced(plant or {}):
            if not trace:
                parts = {"window": _measured(
                    cell, lambda: _window(cell, seconds, 0))}
            else:
                p1 = _measured(cell, lambda: _traced(cell, seconds / 2, 0))
                p2 = _measured(cell, lambda: _host_profiled(
                    cell, seconds / 2, len(p1.ops)))
                parts = {"trace": p1, "host": p2}
    finally:
        gc.callbacks.remove(gc_clock)
        gc.unfreeze()
    mem_after = host_memory()
    compile_after = meter.snapshot()
    in_window = compile_after["compiles"] - compile_setup["compiles"]
    mem = dev.memory_peak_bytes(devices)
    cell.finish()
    t_verify = time.perf_counter()
    checks = cell.verify()
    verify_s = time.perf_counter() - t_verify

    attempted = sum(len(p.ops) + p.failed for p in parts.values())
    failed = sum(p.failed for p in parts.values())
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)

    run = Run(workload, config, traffic, peaks, setup_s, parts)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in registry.metrics_for(bench, workload, kind):
        val = registry.reader(m["name"])(run)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    device["memory_peak_bytes"] = mem if mem is not None else 0
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    p = parts.get("trace")
    if p is not None and p.trace is not None:
        device["busy_s"] = tr_mod.busy_s(p.trace)
        device["window_s"] = tr_mod.window_s(p.trace)
        result["breakdown"] = {
            "device_ops": tr_mod.top(tr_mod.op_seconds(p.trace)),
            "idle_gaps": tr_mod.top(tr_mod.idle_by_span(p.trace))}
    result["host_rss_peak_bytes"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024
    result["compiles"] = {"setup": compile_setup["compiles"],
                          "setup_cache_hits": compile_setup["cache_hits"],
                          "in_window": in_window}
    result["notes"] = {**getattr(cell, "notes", {}), **op_notes(parts),
                       "window_s": sum(p.seconds for p in parts.values()),
                       "gc_s": gc_clock.seconds, "gc_full": gc_clock.full,
                       "verify_s": verify_s,
                       "host_after_window": mem_after}
    log(f"notes {result['notes']}")
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    for name, v, lim in checks:
        log(f"check {name}: {v} (limit {lim})")
    return result
