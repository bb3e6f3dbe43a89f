"""Independent stragglers: each worker straggles on each call with
probability ``q`` (``q = 0``: never).

A traffic file names its straggler process under ``"stragglers"``
(``{"process": "bernoulli", "q": 0.1}``) and the harness loads
``stragglers/<process>.py``.  Every process draws the run's masks from its
seed before the window opens, so nothing is sampled inside it.
"""
from __future__ import annotations

import numpy as np


def worker_masks(spec: dict, n_workers: int, calls: int,
                 seed: int) -> np.ndarray:
    """``(calls, n_workers)`` masks, True where a worker straggles."""
    q = float(spec["q"])
    if not 0.0 <= q < 1.0:
        raise ValueError(f"bernoulli q must be in [0, 1); got {q}")
    rng = np.random.default_rng([seed, 0x5157])
    return rng.random((calls, n_workers)) < q
