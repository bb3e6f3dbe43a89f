"""The worker matvec's share of its roofline: the least time of the
worker's work at the chip's peaks (``work_counts``' ``worker``: read M
once, or the partials once and the symbols once), over its device time."""


def read(r):
    ms = r.summary.module_ms_per_call("worker_program")
    if ms is None:
        return None
    return 100.0 * r.least_ms("worker") / ms
