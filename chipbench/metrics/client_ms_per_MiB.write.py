"""cProfile self time of the fsio client, LOV and OSC per MiB write."""
from chipbench import readers


def read(run):
    return readers.layer_ms_per_mib(run, "client", "write_bytes")
