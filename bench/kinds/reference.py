"""Plain references of the benchmark's configurations.

Nothing here imports the program.  The code is the configuration's (its
arrays as built, from :mod:`bench.kinds.codes`): which coordinates an
erasure pattern leaves unresolved depends on its graph alone, and the
error bound of a recovered value on its weights and the generator.
Values come from the uncoded computation in float32:

* least squares: one gradient step ``θ' = θ − lr·(Mθ − b)`` with the
  coordinates the decode cannot resolve left where they are (Lemma 1's
  zero-filled gradient);
* gradient aggregation: the sum of the shards the decode resolves.

``precision`` is that of the uncoded computation: ``"highest"`` (the
configurations' own) or ``"bf16"`` (operands rounded to bfloat16, products
summed in float32: the control).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["peel_erased", "peel_bound", "gd_step", "shard_sum",
           "F32_OP_ERROR"]

# unit roundoff charged per float32 operation: IEEE f32 rounds to 2^-24,
# and a TPU matmul at HIGHEST emulates f32 within a few ulps; 4 ulps
F32_OP_ERROR = 2.0 ** -22


def peel_erased(h_cols: np.ndarray, erased: np.ndarray,
                rounds: int) -> np.ndarray:
    """Erasures left after ``rounds`` flooding rounds of peeling.

    In each round every check with exactly one erased neighbour resolves
    it, all checks at once.  ``h_cols (p, r)``, ``erased (N,)`` bool.
    """
    e = np.array(erased, bool)
    for _ in range(rounds):
        ne = e[h_cols]                              # (p, r)
        one = ne.sum(axis=1) == 1
        if not one.any():
            break
        e[h_cols[one][ne[one]]] = False
    return e


def peel_bound(h_cols: np.ndarray, h_abs: np.ndarray, erased: np.ndarray,
               mag: np.ndarray, input_error: np.ndarray,
               rounds: int) -> np.ndarray:
    """Per-coordinate float32 error bound of a peeled decode (``inf``
    where a coordinate stays erased), propagated along the flooding order.

    A check resolving ``c_t = −(Σ_s w_s c_s) / a`` turns its sources'
    errors ``ε_s`` into ``Σ_s |w_s|(ε_s + γ|c_s|) / |a| + u|c_t|`` with
    ``γ = (r + 2)·u`` for the products, the r-term sum and the divide;
    received symbols start at ``input_error``.  Where several checks
    resolve one coordinate in a round, the largest bound is taken (the
    decoders may use any of them).  ``h_abs`` holds ``|H|`` at ``h_cols``;
    ``mag`` the codeword's magnitudes.
    """
    u = F32_OP_ERROR
    gamma = (h_cols.shape[1] + 2) * u
    e = np.array(erased, bool)
    err = np.where(e, np.inf, np.asarray(input_error, np.float64))
    for _ in range(rounds):
        ne = e[h_cols]
        rows = np.flatnonzero(ne.sum(axis=1) == 1)
        if rows.size == 0:
            break
        cols, nr = h_cols[rows], ne[rows]
        slot = nr.argmax(axis=1)
        tgt = cols[np.arange(rows.size), slot]
        w = h_abs[rows]
        src = np.where(nr, 0.0, w * (np.where(nr, 0.0, err[cols])
                                     + gamma * mag[cols]))
        b = src.sum(axis=1) / w[np.arange(rows.size), slot] + u * mag[tgt]
        new = np.zeros_like(err)
        np.maximum.at(new, tgt, b)
        err[tgt] = new[tgt]
        e[tgt] = False
    return err


def _matmul(a, b, precision: str):
    if precision == "bf16":
        # operands stored in bfloat16, products summed in float32;
        # reduce_precision is kept by XLA, where a float32 -> bfloat16 ->
        # float32 round trip of converts may be folded away on the TPU
        a, b = (jax.lax.reduce_precision(x, exponent_bits=8,
                                         mantissa_bits=7) for x in (a, b))
    elif precision != "highest":
        raise ValueError(f"no precision {precision!r}")
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("precision",))
def gd_step(M, b, lr, theta, resolved, precision: str = "highest"):
    """``θ − lr·(Mθ − b)`` on the resolved coordinates, ``θ`` elsewhere."""
    g = _matmul(M, theta, precision) - b
    return theta - lr * jnp.where(resolved, g, 0.0)


@partial(jax.jit, static_argnames=("precision",))
def shard_sum(partials, resolved, precision: str = "highest"):
    """``Σ_i resolved_i · partials_i`` over the ``(K, dim)`` shards."""
    return _matmul(resolved.astype(jnp.float32), partials, precision)
