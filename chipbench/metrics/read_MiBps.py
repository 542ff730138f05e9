"""User bytes returned by reads, over all the time of the window."""
from chipbench import readers


def read(run):
    return readers.rate(run, "read_bytes", readers.MiB)
