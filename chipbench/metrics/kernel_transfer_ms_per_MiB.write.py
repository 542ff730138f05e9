"""Time in the program's `kernel.put` (copy in, dispatch) and `kernel.get`
(wait, copy back) spans per MiB written, in the traced part."""
from chipbench import progspans, readers


def read(run):
    return progspans.ms_per(run, ("kernel.put", "kernel.get"),
                            "write_bytes", readers.MiB)
