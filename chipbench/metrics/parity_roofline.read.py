"""The Pallas XOR kernel's share of its HBM roofline: the bytes it needs
over the device's peak HBM bandwidth (`peaks.json`), over its summed
device time."""
from chipbench import readers


def read(run):
    return readers.kernel_roofline_pct(run, "xor_parity")
