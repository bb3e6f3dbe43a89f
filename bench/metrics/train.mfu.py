"""The training step's share of the chip's peak: the least time of the
UNCODED step (the same forward and backward matmuls, one gradient, AdamW)
at the chip's peaks, over the traced time per call.  None where the
profile has no device ops or no training program ran."""


def read(r):
    if (r.summary.busy_s <= 0.0
            or r.summary.module_ms_per_call("train_fwd_program") is None):
        return None
    return 100.0 * r.least_ms("uncoded") / r.step_ms
