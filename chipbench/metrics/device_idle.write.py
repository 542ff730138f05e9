"""Share of the traced window in which no operation ran on the device."""
from chipbench import readers


def read(run):
    return readers.device_idle_pct(run)
