"""Device milliseconds per call of the training step's model programs:
every shard's forward (``train_fwd_program``), the per-layer shard
gradients (``train_grad_program``) and AdamW (``train_opt_program``), from
the profiler trace."""

PROGRAMS = ("train_fwd_program", "train_grad_program", "train_opt_program")


def read(r):
    return r.summary.module_ms_per_call(*PROGRAMS)
