"""Device milliseconds a step spends in the exchange between chips: the
collective ops (all-gather, reduce-scatter, all-reduce; all-to-all, which
the compiler emits for a sharded embedding's lookup and its gradient; and
collective-permute, for matmuls it splits into a ring over the chips;
each with its async start and done halves), by their self time in the
traced part, summed over the devices and divided by the devices and by
the steps. On one device there is none to read."""
import re

from chipbench import trace

COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")


def read(run):
    p = run.parts.get("trace")
    if p is None or p.trace is None or not p.ops or p.trace.n_devices < 2:
        return None
    t = sum(s for name, s in trace.op_seconds(p.trace).items()
            if COLLECTIVE.search(name))
    return 1e3 * t / p.trace.n_devices / len(p.ops) if t > 0 else None
