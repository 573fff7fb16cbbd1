"""Share of the traced window in which no operation runs on the chip,
in % (mean over the chips traced)."""


def read(r):
    if r.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
