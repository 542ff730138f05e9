"""cProfile self time of the kernel wrappers, with the JAX dispatch and
transfers beneath them, per MiB read."""
from chipbench import readers


def read(run):
    return readers.layer_ms_per_mib(run, "kernel_call", "read_bytes")
