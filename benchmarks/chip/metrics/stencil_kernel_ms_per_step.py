"""Device time of the fused stencil kernel per timestep, in ms (mean
over the chips traced)."""


def read(r):
    if r.trace.kernel_s <= 0:
        return None
    return 1e3 * r.trace.kernel_s / r.steps
