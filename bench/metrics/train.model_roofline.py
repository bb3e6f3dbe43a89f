"""The model programs' share of their roofline: the least time of the
step's forward and backward matmuls, the shard gradients written and AdamW
(``work_counts``' ``model``) at the chip's peaks, over the device time per
call of ``train_fwd_program``, ``train_grad_program`` and
``train_opt_program``."""

PROGRAMS = ("train_fwd_program", "train_grad_program", "train_opt_program")


def read(r):
    ms = r.summary.module_ms_per_call(*PROGRAMS)
    if ms is None:
        return None
    return 100.0 * r.least_ms("model") / ms
