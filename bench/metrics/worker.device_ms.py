"""Device milliseconds per call of the worker program (the SPMD matvec
``distributed/worker.py`` launches), from the profiler trace."""


def read(r):
    return r.summary.module_ms_per_call("worker_program")
