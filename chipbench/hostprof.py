"""Host time by program layer, from a cProfile profile.

A function is in the layer whose pattern in `layers.json` matches its
file, the longest pattern winning. A function in no layer (the standard
library, numpy, jax, C builtins) is charged to its callers, in the shares
cProfile recorded for each caller, up to the first caller in a layer; time
with no such caller is the harness's own.
"""
from __future__ import annotations

import json
from pathlib import Path

LAYERS = Path(__file__).resolve().parent / "layers.json"
HARNESS = "harness"


def load_layers(path: Path = LAYERS) -> dict:
    with open(path) as f:
        return json.load(f)


def layer_of(filename: str, layers: dict) -> str | None:
    fn = filename.replace("\\", "/")
    best, best_len = None, 0
    for key, spec in layers["layers"].items():
        for pat in spec["match"]:
            if pat in fn and len(pat) > best_len:
                best, best_len = key, len(pat)
    return best


def _norm(weights: dict) -> dict:
    tot = sum(w for w in weights.values() if w > 0)
    return {c: w / tot for c, w in weights.items() if w > 0} if tot else {}


def _mix(weights: dict, value) -> dict:
    out: dict[str, float] = {}
    for c, w in weights.items():
        for lay, x in value(c).items():
            out[lay] = out.get(lay, 0.0) + w * x
    return out


def by_layer(stats: dict, layers: dict, rounds: int = 200) -> dict:
    """Self seconds by layer. `stats` is `pstats.Stats(...).stats`:
    {func: (cc, nc, tt, ct, {caller: (cc, nc, tt, ct)})}.

    A function in no layer has, for the time under it, the layer shares of
    its callers weighted by the cumulative time each caller spent in it;
    through recursion these shares are a fixed point, found by iteration.
    Its own self time is split by the self time each caller spent in it."""
    own = {f: layer_of(f[0], layers) for f in stats}
    free = [f for f in stats if own[f] is None]
    by_ct = {f: _norm({c: v[3] for c, v in stats[f][4].items()})
             for f in free}
    share: dict = {f: {} for f in free}

    def value(c) -> dict:
        if c in share:
            return share[c] if by_ct[c] else {HARNESS: 1.0}
        lay = own.get(c) or layer_of(c[0], layers)
        return {lay or HARNESS: 1.0}

    for _ in range(rounds):
        new = {f: _mix(by_ct[f], value) for f in free}
        change = max((abs(new[f].get(k, 0.0) - share[f].get(k, 0.0))
                      for f in free for k in set(new[f]) | set(share[f])),
                     default=0.0)
        share = new
        if change < 1e-12:
            break

    totals: dict[str, float] = {}
    for func, (_, _, tt, _, callers) in stats.items():
        if tt <= 0:
            continue
        if own[func] is not None:
            parts = {own[func]: 1.0}
        else:
            by_tt = _norm({c: v[2] for c, v in callers.items()})
            parts = _mix(by_tt, value) if by_tt else value(func)
        left = 1.0 - sum(parts.values())
        if left > 1e-9:       # time under a recursion with no way out
            parts[HARNESS] = parts.get(HARNESS, 0.0) + left
        for lay, x in parts.items():
            totals[lay] = totals.get(lay, 0.0) + tt * x
    return totals


def cumulative(stats: dict, filename_suffix: str, funcname: str) -> float:
    """Cumulative seconds of one function (outermost calls only, as
    cProfile counts them)."""
    return sum(v[3] for f, v in stats.items()
               if f[2] == funcname and f[0].replace("\\", "/").endswith(
                   filename_suffix))
