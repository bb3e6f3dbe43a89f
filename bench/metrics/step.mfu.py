"""The whole step's share of the chip's peak: the least time of the
UNCODED computation (the plain gradient, or the plain sum of the
partials) at the chip's peaks, over the traced time per call.  It bounds
any gain, also one that takes a kernel off the path."""


def read(r):
    if r.summary.busy_s <= 0.0:
        return None
    return 100.0 * r.least_ms("uncoded") / r.step_ms
