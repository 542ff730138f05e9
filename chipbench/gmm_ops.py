"""The device time of the grouped-matmul kernels (`gmm`, `tgmm`) in a
run's traced part, which `metrics/moe_gmm_ms_per_step.py` and
`metrics/gmm_roofline.py` read."""
from __future__ import annotations

import re

from chipbench import trace

# the kernels' names as the compiler gives them to the device ops: the
# jitted megablox function's name under the transforms that reached it
GMM = re.compile(r"(?:^|_)t?gmm(?:_|$)")


def seconds(run) -> float | None:
    """Self seconds of the gmm and tgmm ops in the traced part, summed over
    the devices; None where there is none."""
    p = run.parts.get("trace")
    if p is None or p.trace is None or not p.ops:
        return None
    t = sum(s for name, s in trace.op_seconds(p.trace).items()
            if GMM.search(name))
    return t if t > 0 else None
