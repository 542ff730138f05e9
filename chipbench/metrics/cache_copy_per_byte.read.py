"""Bytes the OSC caches copy (the `copied` count of the `osc.io` spans:
dirty-extent coalescing and clean-extent inserts) per byte read, in the
traced part."""
from chipbench import progspans


def read(run):
    return progspans.count_per(run, "osc.io", "copied", "read_bytes")
