"""Coded all-reduce of one data-parallel gradient bucket: the
configuration kind's data and work counts.

``K`` shard gradients of ``dim`` float32 each are summed under the
configuration's LDPC code, one symbol per worker.  Data, from the run's
seed and on the device in one jitted call: two ``(K, dim)`` sets of
N(0, 1) partials, used in alternation.  The drivers that run it are
``drivers/gradagg.<driver>.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["work_counts", "make_partials"]

_F32 = 4


def work_counts(cfg: dict) -> dict:
    """Least bytes and operations of each layer, from the shapes alone:

    * worker: read the K partials, write the N coded symbols; the parity
      symbols are combinations of the K partials (2·(N−K)·K·dim ops);
    * master: read the N symbols, write the K recovered shards;
    * uncoded: the plain sum of K partials (read K, write one).
    """
    K, N, dim = cfg["K"], cfg["N"], cfg["dim"]
    return {"worker": {"bytes": (K + N) * dim * _F32,
                       "flops": 2 * (N - K) * K * dim},
            "master": {"bytes": (N + K) * dim * _F32, "flops": 0},
            "uncoded": {"bytes": (K + 1) * dim * _F32, "flops": K * dim}}


@partial(jax.jit, static_argnames=("K", "dim"))
def make_partials(key, K: int, dim: int):
    """Two ``(K, dim)`` sets of N(0, 1) partials from one key."""
    ka, kb = jax.random.split(key)
    return (jax.random.normal(ka, (K, dim), jnp.float32),
            jax.random.normal(kb, (K, dim), jnp.float32))
