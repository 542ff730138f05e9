"""Device milliseconds a step spends in the held experts' grouped matmuls:
the self time of the `gmm` and `tgmm` kernels (JAX's Pallas megablox,
named so by the program's compile: `jvp_jit_gmm__`,
`transpose_jvp_jit_tgmm___` and the like) in the traced part, summed over
the devices, over the devices and the steps."""
from chipbench import gmm_ops


def read(run):
    p = run.parts.get("trace")
    t = gmm_ops.seconds(run)
    if t is None:
        return None
    return 1e3 * t / p.trace.n_devices / len(p.ops)
