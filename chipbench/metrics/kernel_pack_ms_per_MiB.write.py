"""Self time of the program's `kernel.parity` spans, the numpy packing and
unpacking around the parity kernel, per MiB written, in the traced part."""
from chipbench import progspans, readers


def read(run):
    return progspans.ms_per(run, ("kernel.parity",), "write_bytes",
                            readers.MiB)
