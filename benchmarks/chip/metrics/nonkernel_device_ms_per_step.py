"""Device time outside the stencil kernel and outside collectives, per
timestep, in ms (mean over the chips traced): the pipeline's own
operations, such as the loop-carry copy of the store and, on a mesh,
face packing and unpacking."""


def read(r):
    if r.trace.busy_s <= 0:
        return None
    return 1e3 * r.trace.other_s / r.steps
