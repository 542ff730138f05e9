"""Bytes of the dead OST's tail unit that the LOV rebuilt to learn a
degraded raid5 file's size (the `size_rebuilt_bytes` count of the `fs.io`
spans) per byte read, in the traced part. None where no `fs.io` span
carries that count: a program that does not count it, or a read of no
degraded file."""
from chipbench import progspans


def read(run):
    s = progspans.summary(run)
    if s is None or "size_rebuilt_bytes" not in s.get(
            "fs.io", {}).get("counts", {}):
        return None
    return progspans.count_per(run, "fs.io", "size_rebuilt_bytes",
                               "read_bytes")
