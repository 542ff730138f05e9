"""Model FLOPs of the steps in the traced part, over the device's busy time
in that part (the union of its operations in the profiler's trace,
averaged over the devices), over the bf16 peak of that many chips
(`peaks.json`): the step's share of the peak while the devices run it,
the same quantity on one chip or four. The host's gaps between steps are
`device_idle.train`. The FLOPs per token are the architecture's
(`archs/`): the forward and backward matmuls, the head and attention, and
no recomputation."""
from chipbench import registry, trace


def read(run):
    p = run.parts.get("trace")
    if p is None or not p.ops or p.trace is None or run.peaks is None:
        return None
    busy = trace.busy_s(p.trace)
    if busy <= 0:
        return None
    seq = run.config["training"]["seq_length"]
    flops = registry.arch(run.config).train_flops_per_token(
        run.config, seq) * p.amount("tokens")
    peak = run.peaks["bf16_flops"] * p.trace.n_devices
    return 100.0 * flops / busy / peak
