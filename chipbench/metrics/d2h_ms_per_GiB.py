"""Time in the program's `ckpt.d2h` spans, the save's device-to-host copy,
per GiB of state saved, in the traced part."""
from chipbench import progspans, readers


def read(run):
    return progspans.ms_per(run, ("ckpt.d2h",), "write_bytes", readers.GiB)
