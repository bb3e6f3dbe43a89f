"""Device milliseconds per call of the master side: the decode + update
program and any late-fold program, from the profiler trace."""


def read(r):
    return r.summary.module_ms_per_call("master_program", "fold_program")
