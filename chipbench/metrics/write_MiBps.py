"""User bytes acknowledged durable, over all the time of the window."""
from chipbench import readers


def read(run):
    return readers.rate(run, "write_bytes", readers.MiB)
