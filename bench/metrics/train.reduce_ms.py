"""Device milliseconds per call of the training step's coded reduce: every
bucket's ``reduce_program`` (parity symbols, erase, peeling decode,
zero-fill and sum), from the profiler trace."""


def read(r):
    return r.summary.module_ms_per_call("reduce_program")
