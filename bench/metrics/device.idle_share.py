"""Share of the traced window in which no operation ran on the device:
1 − (union of device op intervals) / window, in percent."""


def read(r):
    if r.summary.busy_s <= 0.0:
        return None
    return 100.0 * r.summary.idle_share
