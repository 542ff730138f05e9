"""Self time of the program's `fs.io`, `lov.io` and `osc.io` spans per MiB
read, in the traced part."""
from chipbench import progspans, readers


def read(run):
    return progspans.ms_per(run, ("fs.io", "lov.io", "osc.io"),
                            "read_bytes", readers.MiB)
