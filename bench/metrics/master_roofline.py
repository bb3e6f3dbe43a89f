"""The master decode's share of its roofline: the least bytes any decoder
moves (read the N symbols, write the K recovered ones) at the HBM
bandwidth, over the master's device time per call."""


def read(r):
    ms = r.summary.module_ms_per_call("master_program", "fold_program")
    if ms is None:
        return None
    return 100.0 * r.least_ms("master") / ms
