"""Collective time during which no other operation runs on the chip,
per timestep, in ms (mean over the chips traced): the halo exchange
that the compute does not hide. Nothing when the trace holds no
collective."""


def read(r):
    if r.trace.collective_s <= 0:
        return None
    return 1e3 * r.trace.exposed_collective_s / r.steps
