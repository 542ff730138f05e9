"""Model FLOPs of the steps in the traced part, over the device's busy time
in that part (the union of its operations in the profiler's trace), over
the chip's bf16 peak (`peaks.json`): the step's share of the peak while
the device runs it. The host's gaps between steps are `device_idle.train`.
The FLOPs per token count the forward and backward matmuls, the tied head
and attention, and no recomputation."""
from chipbench import costs, trace


def read(run):
    p = run.parts.get("trace")
    if p is None or not p.ops or p.trace is None or run.peaks is None:
        return None
    busy = trace.busy_s(p.trace)
    if busy <= 0:
        return None
    seq = run.config["training"]["seq_length"]
    flops = costs.train_flops_per_token(run.config, seq) * p.amount("tokens")
    return 100.0 * flops / busy / run.peaks["bf16_flops"]
