"""cProfile self time of the fsio client, LOV and OSC per MiB read."""
from chipbench import readers


def read(run):
    return readers.layer_ms_per_mib(run, "client", "read_bytes")
