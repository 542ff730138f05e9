"""The held experts' grouped matmuls against the chip's bf16 peak: the
FLOPs the architecture counts for them (`gmm_flops` of
`archs/<architecture>.py`: forward, the rematerialized forward, and both
gradients) over the (token, expert) pairs the program routed to the held
experts in the traced part (its `train.counts` span's `held_pairs`), over
their device time, over the peak of the devices. A program that does not
count its held pairs gives nothing. At this configuration's widths they
are bound by arithmetic: a call moves the held rows in and out and the
held experts' weights once, about 395 FLOPs a byte at 8192 held pairs,
above the v5e's ridge of 240 (197 TFLOP/s over 819 GB/s)."""
from chipbench import gmm_ops, progspans, registry


def read(run):
    t = gmm_ops.seconds(run)
    s = progspans.summary(run)
    if t is None or s is None or run.peaks is None:
        return None
    pairs = s.get("train.counts", {}).get("counts", {}).get("held_pairs")
    if not pairs:
        return None
    flops = registry.arch(run.config).gmm_flops(run.config, pairs)
    return 100.0 * flops / t / run.peaks["bf16_flops"]
