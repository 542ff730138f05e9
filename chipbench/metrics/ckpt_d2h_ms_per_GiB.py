"""cProfile cumulative time of the save's device-to-host copy
(`_host_bytes`) per GiB of state saved."""
from chipbench import readers


def read(run):
    return readers.cumulative_ms_per(run, "repro/ckpt/checkpoint.py",
                                     "_host_bytes", "write_bytes",
                                     readers.GiB)
