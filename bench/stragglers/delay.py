"""Shifted-exponential worker latencies: each worker's delay on each step
is ``tau + Exp(mu)`` (:class:`repro.core.straggler.DelayModel`'s law), and
a worker is late where it is slower than the cut the driver waits for.

A traffic file names the process with its parameters and the steps of one
call (``{"process": "delay", "tau": 1.0, "mu": 1.0, "chunk": 200}``).  The
run's delays are drawn from the seed before the window opens: one
``(chunk, workers)`` table per call, float32, for at most ``POOL`` calls
(the harness wraps round the pool; a window of the manifest's length makes
a few dozen calls of a chunk at most).
"""
from __future__ import annotations

import numpy as np

POOL = 64


def worker_masks(spec: dict, n_workers: int, calls: int,
                 seed: int) -> np.ndarray:
    """``(min(calls, POOL), chunk, n_workers)`` float32 delays."""
    tau, mu = float(spec["tau"]), float(spec["mu"])
    if tau < 0.0 or mu <= 0.0:
        raise ValueError(f"delay needs tau >= 0 and mu > 0; got {tau}, {mu}")
    rng = np.random.default_rng([seed, 0x64656C])
    shape = (min(calls, POOL), int(spec["chunk"]), n_workers)
    return (tau + rng.exponential(1.0 / mu, shape)).astype(np.float32)
