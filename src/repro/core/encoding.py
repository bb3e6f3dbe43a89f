"""Moment encoding (the paper's preprocessing step).

Given data ``X in R^{m x k}`` and labels ``y in R^m``, the gradient of the
squared loss is ``∇L(θ) = M θ - b`` with ``M = X^T X`` and ``b = X^T y``.
``M`` is computed ONCE and encoded:

* Scheme 2 (``K == k``): ``C = G @ M in R^{N x k}``; worker ``j`` stores row
  ``c_j`` and computes the scalar ``⟨c_j, θ⟩`` per step.  ``C θ`` is a
  codeword whose first ``k`` coordinates are ``M θ`` (systematic G).

* Scheme 1 (``K | k``): the rows of ``M`` are partitioned into ``k/K``
  blocks, each encoded separately: ``C^(i) = G M_{P_i}``; worker ``j`` holds
  row ``j`` of every block (α = k/K rows total) and returns α scalars.

Encoding cost is one (N x K) @ (K x k) matmul — the Pallas ``block_matmul``
kernel covers this at scale; here the jnp path is the reference.

SEEDED encode: for a seeded LDGM code (:func:`repro.core.ldpc.make_seeded_ldgm`)
the generator rows are recomputable from ``(seed, row)`` in O(row_weight), so
``C = G @ M`` reduces to per-row gathers over M (:func:`encode_moment_seeded`)
and the per-step codeword ``C θ`` to a gather over ``y = M θ``
(:func:`gather_encode`) — no generator or encoding-matrix rows are ever
materialized.  The same gather tables drive the sharded worker encode
(``distributed/worker.local_products_seeded``), so single-device and
distributed products are bit-identical.

FUSED seeded encode (:func:`encode_seeded`): the gather itself moves into a
Pallas kernel (``encode_seeded_fused``) that regenerates each row's
(column, weight) pairs in-register, so not even the ``(N, r+1)`` index
tables exist.  :func:`gather_encode` runs its sum SEQUENTIALLY in table
order for exactly this reason: under jit, XLA:CPU contracts each
multiply-add into an FMA the same way inside and outside the kernel, so the
fused kernel is bit-identical to the jit-compiled table gather (a
``(g * c).sum(axis=1)`` reduction would sum in a different association
order and only match to ~1 ulp).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.lax import Precision

from repro.core.ldpc import (LDPCCode, seeded_generator_rows,
                             seeded_structure)

__all__ = ["Moments", "second_moment", "encode_moment",
           "encode_moment_blocks", "encode_moment_seeded", "gather_encode",
           "generator_gather_tables", "encode_seeded",
           "generator_structure_of"]


class Moments(NamedTuple):
    M: jax.Array  # (k, k)
    b: jax.Array  # (k,)


def second_moment(X: jax.Array, y: jax.Array) -> Moments:
    """M = X^T X, b = X^T y — the one-time preprocessing pass."""
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    return Moments(jnp.matmul(X.T, X, precision=Precision.HIGHEST),
                   jnp.matmul(X.T, y, precision=Precision.HIGHEST))


def encode_moment(code: LDPCCode, M: jax.Array) -> jax.Array:
    """Scheme 2 encode: C = G @ M, shape (N, k); requires code.K == k."""
    M = jnp.asarray(M)
    if code.K != M.shape[0]:
        raise ValueError(f"code dimension K={code.K} != k={M.shape[0]}; "
                         "use encode_moment_blocks for K | k")
    G = jnp.asarray(code.G, M.dtype)
    return jnp.matmul(G, M, precision=Precision.HIGHEST)


def encode_moment_blocks(code: LDPCCode, M: jax.Array) -> jax.Array:
    """Scheme 1 encode: stack of per-block codeword matrices.

    Returns ``C`` of shape (k/K, N, k): ``C[i] = G @ M[i*K:(i+1)*K]``.
    Worker ``j`` is assigned ``C[:, j, :]`` (α = k/K rows).
    """
    M = jnp.asarray(M)
    k = M.shape[0]
    if k % code.K != 0:
        raise ValueError(f"K={code.K} must divide k={k}")
    nb = k // code.K
    G = jnp.asarray(code.G, M.dtype)
    blocks = M.reshape(nb, code.K, k)
    return jnp.einsum("nk,bkj->bnj", G, blocks, precision=Precision.HIGHEST)


def generator_gather_tables(code: LDPCCode) -> tuple[jax.Array, jax.Array]:
    """Full-generator gather tables of a seeded LDGM code, as jnp arrays.

    ``(idx (N, row_weight) int32, coeff (N, row_weight) f32)`` with
    ``G[i] = Σ_s coeff[i, s]·e_{idx[i, s]}`` — the whole generator in
    ``O(N·row_weight)`` ints instead of an ``(N, K)`` dense matrix.
    """
    idx, coeff = seeded_generator_rows(code, 0, code.N)
    return jnp.asarray(idx), jnp.asarray(coeff)


def gather_encode(idx: jax.Array, coeff: jax.Array,
                  y: jax.Array) -> jax.Array:
    """THE seeded per-row encode: ``z[i] = Σ_s coeff[i, s] · y[idx[i, s]]``.

    ``y`` is ``(K,)`` or ``(K, V)``; returns ``(n,)`` / ``(n, V)`` for
    tables of ``n`` rows.  Zero-weight pad slots gather row ``idx=0`` with
    coefficient 0 — exact zeros, no sentinel row needed.  Single-device
    encodes and each sharded worker's fused encode-matvec run this same
    gather+sum over their row ranges, so their products are bit-identical.

    The sum is SEQUENTIAL in table-slot order: under jit this lowers to
    the same FMA chain as the fused Pallas encode kernel
    (``kernels.ldpc_peel.encode_seeded_fused``), making the two
    bit-identical — the load-bearing property behind every
    materialized-vs-fused encode parity check.
    """
    yj = jnp.asarray(y)
    c = coeff.astype(yj.dtype)
    if yj.ndim == 2:
        c = c[..., None]
    out = c[:, 0] * yj[idx[:, 0]]
    for s in range(1, idx.shape[1]):
        out = out + c[:, s] * yj[idx[:, s]]
    return out


def encode_moment_seeded(code: LDPCCode, M: jax.Array) -> jax.Array:
    """Scheme 2 encode ``C = G @ M`` via the seeded generator gathers.

    Same shape contract as :func:`encode_moment` (``(N, k)``, requires
    ``code.K == k``) but the generator is never materialized: each codeword
    row is a ``row_weight``-term gather+sum over rows of ``M`` —
    ``O(N·row_weight·k)`` work and ``O(N·row_weight)`` structure ints
    instead of an ``(N, K)`` dense ``G``.  Requires a
    :func:`repro.core.ldpc.make_seeded_ldgm` code.
    """
    M = jnp.asarray(M)
    if code.K != M.shape[0]:
        raise ValueError(f"code dimension K={code.K} != k={M.shape[0]}; "
                         "use encode_moment_blocks for K | k")
    idx, coeff = generator_gather_tables(code)
    return gather_encode(idx, coeff, M)


def generator_structure_of(code: LDPCCode):
    """The :class:`repro.core.ldpc.SeededStructure` of a seeded LDGM code's
    generator parity block ``P`` (``G = [I; P]``) — the static spec the
    fused encode kernel regenerates rows from."""
    kind = getattr(code, "kind", None)
    if kind != "ldgm-seeded":
        raise ValueError(
            f"fused seeded encode needs a make_seeded_ldgm code "
            f"(kind='ldgm-seeded'); got kind={kind!r}")
    return seeded_structure(code.p, code.K, code.r - 1, code.seed)


def encode_seeded(code: LDPCCode, y: jax.Array, row0=0, *,
                  n_out: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Codeword rows ``[row0, row0 + n_out)`` of ``G @ y`` via the FUSED
    seeded encode kernel — no gather tables, no generator.

    ``y`` is ``(K,)`` or ``(K, V)``; ``row0`` may be traced (sharded
    workers pass their row offset); ``n_out`` defaults to the full
    codeword ``N``.  Bit-identical to the (jit-compiled)
    :func:`gather_encode` over :func:`generator_gather_tables` rows —
    see the module docstring for why the summation orders agree.
    """
    from repro.kernels.ldpc_peel.ops import encode_seeded_fused_pallas
    st = generator_structure_of(code)
    if n_out is None:
        n_out = code.N
    return encode_seeded_fused_pallas(st, y, row0, n_out=n_out,
                                      interpret=interpret)
