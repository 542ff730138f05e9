"""Tokens trained over all the time of the window, every step's batch
read through the file system."""
from chipbench import readers


def read(run):
    return readers.rate(run, "tokens", 1.0)
