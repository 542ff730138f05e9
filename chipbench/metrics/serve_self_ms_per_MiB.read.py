"""Self time of the program's `rpc.serve` spans (the server handlers, NRS,
OST and obd beneath ptlrpc) per MiB read, in the traced part."""
from chipbench import progspans, readers


def read(run):
    return progspans.ms_per(run, ("rpc.serve",), "read_bytes", readers.MiB)
