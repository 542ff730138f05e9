"""Self time of the program's `kernel.parity` spans, the numpy packing and
unpacking around the parity kernel, per MiB read, in the traced part."""
from chipbench import progspans, readers


def read(run):
    return progspans.ms_per(run, ("kernel.parity",), "read_bytes",
                            readers.MiB)
