"""Milliseconds a step waits in the program's `train.batch` span (the batch
read through fsio, its labels and the host-to-device copy), by the
profiler-clock spans of the traced part."""
from chipbench import progspans


def read(run):
    p = run.parts.get("trace")
    s = progspans.summary(run)
    if s is None or "train.batch" not in s or not p.ops:
        return None
    return 1e3 * s["train.batch"]["total_s"] / len(p.ops)
