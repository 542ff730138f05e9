"""cProfile self time of ptlrpc, NRS, OST, obd and the rest of core per MiB
write."""
from chipbench import readers


def read(run):
    return readers.layer_ms_per_mib(run, "server", "write_bytes")
