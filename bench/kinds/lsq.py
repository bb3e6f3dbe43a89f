"""Least squares by moment-encoded GD (arXiv:1805.08327, Scheme 2): the
configuration kind's data and work counts.

Data, from the run's seed and on the device in one jitted call: ``X``
(m × k, entries N(0, 1/m)), ``θ*`` ~ N(0, I), ``y = Xθ*``; then ``M =
XᵀX``, ``b = Xᵀy`` and ``lr = 1/λ_max(M)`` by power iteration, and ``X``
is dropped.  The code is the configuration's (:mod:`bench.kinds.codes`).
The drivers that run it are ``drivers/lsq.<driver>.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["work_counts", "make_data"]

_POWER_ITERS = 64
_F32 = 4


def work_counts(cfg: dict) -> dict:
    """Least bytes and operations of each layer, from the shapes alone
    (whatever decode backend or worker encode the configuration picks):

    * worker: the products ``G·M·θ`` need ``M`` read once (k² f32) and
      2·N·k operations;
    * master: read the N survivors, write the K recovered values;
    * uncoded: the plain gradient ``Mθ − b``: ``M`` read once, 2k² ops.
    """
    k, N = cfg["k"], cfg["N"]
    return {"worker": {"bytes": k * k * _F32, "flops": 2 * N * k},
            "master": {"bytes": (N + k) * _F32, "flops": 0},
            "uncoded": {"bytes": k * k * _F32, "flops": 2 * k * k}}


@partial(jax.jit, static_argnames=("m", "k"))
def make_data(key, m: int, k: int):
    """``(M, b, lr)`` of a seeded problem, ``X`` never leaving the call."""
    kx, kt, kv = jax.random.split(key, 3)
    X = jax.random.normal(kx, (m, k), jnp.float32) / np.sqrt(m)
    y = X @ jax.random.normal(kt, (k,), jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    M = jnp.matmul(X.T, X, precision=hi)
    b = jnp.matmul(X.T, y, precision=hi)

    def power(_, v):
        w = jnp.matmul(M, v, precision=hi)
        return w / jnp.linalg.norm(w)

    v = jax.lax.fori_loop(0, _POWER_ITERS, power,
                          jax.random.normal(kv, (k,), jnp.float32))
    lam = v @ jnp.matmul(M, v, precision=hi)
    return M, b, 1.0 / lam
