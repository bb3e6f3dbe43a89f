"""The coded reduce's share of its roofline: the least bytes of gradagg's
encode and decode at the gradient's length (``work_counts``' ``reduce``)
at the HBM bandwidth, over the device time per call of the
``reduce_program`` launches."""


def read(r):
    ms = r.summary.module_ms_per_call("reduce_program")
    if ms is None:
        return None
    return 100.0 * r.least_ms("reduce") / ms
