"""Device gate, peaks by device kind, and the compile meter.

A measurement needs the chip: there is no CPU fallback. Peaks come only
from `peaks.json`, keyed by the `device_kind` JAX reports; a kind that is
not in the table is an error, not a default.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class DeviceError(RuntimeError):
    pass


def gate(devices, chips: int) -> dict:
    """The device record for the run, or DeviceError when the devices are
    not TPUs or are fewer than the cell asks for."""
    if not devices:
        raise DeviceError("no device")
    first = devices[0]
    if first.platform != "tpu":
        raise DeviceError(f"needs a TPU, found {first.platform!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, found "
                          f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": chips}


def peaks(kind: str, path: Path = PEAKS) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise DeviceError(f"no peaks for device kind {kind!r} in {path.name}")
    return table[kind]


def memory_peak_bytes(devices) -> int | None:
    """`peak_bytes_in_use` of the fullest device, where it is reported."""
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit is counted as a compile that took the
    time of the cache read)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
