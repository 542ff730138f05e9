"""Finds what a cell needs by the names `BENCHMARK.json` gives.

A configuration is `configs/<name>.json` (named by the entry's `file`), a
traffic mix is `traffic/<name>.json`, the code that drives a mix is
`drivers/<kind>.py` for the `kind` the mix names, and a metric's reader
is `metrics/<metric name>.py`. What depends on a model configuration's
architecture (its model config for the program, FLOPs a token, plain
reference, norm leaves) is `archs/<architectures[0]>.py`. Adding a cell,
a metric or an architecture adds files and entries; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownName(LookupError):
    pass


def _checked(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise UnknownName(f"not a valid name: {name!r}")
    return name


def load_benchmark(path: Path = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise UnknownName(f"no {what} named {name!r}")


def workload(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], _checked(name), "workload")


def config(bench: dict, name: str) -> dict:
    entry = _entry(bench["configs"], _checked(name), "configuration")
    path = (ROOT / entry["file"]).resolve()
    if HERE not in path.parents:
        raise UnknownName(f"configuration file outside the benchmark: {path}")
    with open(path) as f:
        return json.load(f)


def traffic(name: str) -> dict:
    path = HERE / "traffic" / f"{_checked(name)}.json"
    if not path.is_file():
        raise UnknownName(f"no traffic mix named {name!r}")
    with open(path) as f:
        return json.load(f)


def _load(path: Path, what: str):
    if not path.is_file():
        raise UnknownName(f"no {what} at {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{what}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    """The module that drives traffic of this kind (`drivers/<kind>.py`)."""
    return _load(HERE / "drivers" / f"{_checked(kind)}.py", "driver")


def arch(cfg: dict):
    """The module of a model configuration's architecture
    (`archs/<architectures[0]>.py`)."""
    return _load(HERE / "archs" / f"{_checked(cfg['architectures'][0])}.py",
                 "arch")


def reader(metric: str):
    """The `read(run)` function of this metric (`metrics/<name>.py`)."""
    return _load(HERE / "metrics" / f"{_checked(metric)}.py", "metric").read


def metrics_for(bench: dict, wl: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` metrics this workload reports: those
    that list it under `workloads`, and those that list no workloads."""
    return [m for m in bench[kind]
            if "workloads" not in m or wl in m["workloads"]]
