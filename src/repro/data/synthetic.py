"""Synthetic data generators matching the paper's experimental setup, plus a
deterministic token pipeline for the transformer zoo.

Paper Section 4: X has i.i.d. random entries; y = X θ* (+ optional noise);
θ* dense (least squares) or u-sparse (sparse recovery), with both
overdetermined (m = 2048 > k) and underdetermined (m = 1024 < k = 2000)
regimes.
"""
from __future__ import annotations

from typing import Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg

__all__ = ["LinearProblem", "make_linear_problem", "make_sparse_problem", "token_batches"]


class LinearProblem(NamedTuple):
    X: jax.Array          # (m, k)
    y: jax.Array          # (m,)
    theta_star: jax.Array  # (k,)
    # suggested PGD learning rate: 1/λ_max(X^T X) (guaranteed descent for exact GD)
    lr: float


def _lr_for(X: np.ndarray) -> float:
    # λ_max(X^T X) as the top eigenvalue of the smaller Gram matrix — the
    # same number as ||X||_2^2 without an SVD of the whole X (minutes at
    # m = 32768, k = 8192; this takes seconds).
    gram = X.T @ X if X.shape[0] >= X.shape[1] else X @ X.T
    n = gram.shape[0]
    lam = scipy.linalg.eigh(gram, eigvals_only=True,
                            subset_by_index=[n - 1, n - 1])[0]
    return float(1.0 / lam)


def make_linear_problem(m: int, k: int, *, noise: float = 0.0, seed: int = 0,
                        normalize: bool = True) -> LinearProblem:
    """Dense least squares: X ~ N(0, 1/m)^{m x k}, y = X θ* + noise."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, k))
    if normalize:
        X /= np.sqrt(m)
    theta = rng.standard_normal(k)
    y = X @ theta + noise * rng.standard_normal(m)
    return LinearProblem(jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32),
                         jnp.asarray(theta, jnp.float32), _lr_for(X))


def make_sparse_problem(m: int, k: int, u: int, *, seed: int = 0,
                        normalize: bool = True) -> LinearProblem:
    """u-sparse θ*; covers both m > k (overdetermined) and m < k (IHT)."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((m, k))
    if normalize:
        X /= np.sqrt(m)
    theta = np.zeros(k)
    support = rng.choice(k, size=u, replace=False)
    theta[support] = rng.standard_normal(u)
    y = X @ theta
    return LinearProblem(jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32),
                         jnp.asarray(theta, jnp.float32), _lr_for(X))


def token_batches(vocab: int, batch: int, seq: int, *, seed: int = 0,
                  n_batches: int | None = None) -> Iterator[dict]:
    """Deterministic synthetic token stream for LLM training/smoke tests.

    Yields {"tokens": (batch, seq) int32, "labels": shifted} —
    a Zipf-ish distribution so losses are non-degenerate.
    """
    key = jax.random.PRNGKey(seed)
    i = 0
    while n_batches is None or i < n_batches:
        key, k1 = jax.random.split(key)
        # Zipf-ish: exponentiate a uniform to skew towards small ids.
        u = jax.random.uniform(k1, (batch, seq + 1), minval=1e-6, maxval=1.0)
        toks = jnp.minimum((u ** 3.0) * vocab, vocab - 1).astype(jnp.int32)
        yield {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        i += 1
