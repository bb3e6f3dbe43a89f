"""The paper's coded PGD steps (Schemes 1 and 2), as jit-able JAX functions.

Scheme 2 (the main contribution) per step ``t``:

  1. worker products:   z = C θ_{t-1}            (each worker: one scalar/row)
  2. erasures:          z_S  — stragglers' coordinates masked
  3. peeling decode:    D rounds; unresolved set U_t
  4. zero-fill:         ĉ (and b̂) zeroed on U_t
  5. update:            θ_t = P_Θ(θ_{t-1} - η (ĉ_{1:k} - b̂))

Steps 2–4 are exactly the :class:`repro.core.engine.CodedComputeEngine`
pipeline (erase → decode → epilogue); the schemes here are thin clients
that own the encoded operator ``C`` / moment vector ``b`` and the update
rule, and delegate everything code-related to the engine.  The engine's
batch axis also gives Scheme 2 a batched query path
(:meth:`Scheme2.gradient_batch`): B concurrent (θ, straggler-mask) queries,
one decode launch — the serving primitive behind
:mod:`repro.serving.coded_queries`.

Under Assumption 1 this is PSGD with an unbiased (1-q_D)-scaled gradient
(Lemma 1) and converges at RB/((1-q_D)√T) (Theorem 1).  An optional
``debias`` flag divides the estimate by (1-q_D) — a beyond-paper knob that
makes the estimate exactly unbiased (the paper folds the scale into the
effective learning rate instead).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.lax import Precision

from repro.core import density_evolution
from repro.core.encoding import (Moments, encode_moment,
                                 encode_moment_blocks, encode_seeded,
                                 gather_encode, generator_gather_tables)
from repro.core.engine import CodedComputeEngine, blocked_epilogue
from repro.core.ldpc import LDPCCode
from repro.optim import projections

__all__ = ["Scheme2", "Scheme2Blocked", "Scheme1", "run_pgd", "RunResult"]


class RunResult(NamedTuple):
    theta: jax.Array          # final iterate
    theta_bar: jax.Array      # running average (Theorem 1 is stated for it)
    errors: jax.Array         # (T,) ||theta_t - theta*|| if theta_star given, else loss
    unresolved: jax.Array     # (T,) |U_t| — decode quality per step


@dataclasses.dataclass(frozen=True)
class Scheme2:
    """LDPC moment-encoded approximate-gradient PGD (paper Scheme 2)."""

    code: LDPCCode
    C: jax.Array  # (N, k) encoded moment  = G @ M
    b: jax.Array  # (k,)  = X^T y
    lr: float
    decode_iters: int = 10
    adaptive: bool = False
    decode_backend: str = "auto"  # dense | sparse | pallas | auto (decoder.py)
    projection: Callable[[jax.Array], jax.Array] = projections.identity
    debias: bool = False
    q0_for_debias: float = 0.1
    # Seeded on-the-fly encode: ``C`` holds the RAW (k, k) moment matrix M
    # and every step computes the codeword as a generator gather over
    # ``y = M θ`` — the (N, k) encoded matrix is never materialized, and the
    # per-row gather+sum is the SAME one the sharded workers run
    # (bit-identical products to the distributed runtime).
    seeded_encode: bool = False
    # With ``encode_fused=True`` the generator gather runs inside the fused
    # Pallas encode kernel (:func:`repro.core.encoding.encode_seeded`):
    # gather indices regenerate in-register, so not even the (N, r+1)
    # tables exist.  Bit-identical to the table gather under jit (the
    # kernel and the sequential ``gather_encode`` lower to the same FMA
    # chain) — and to the ``worker_encode="seeded-fused"`` distributed
    # runtime, which runs the same kernel per shard.
    encode_fused: bool = False
    # ``decode_backend="replay"`` only: the cross-step LRU of compiled
    # peeling schedules (:class:`repro.core.schedule_cache.ScheduleCache`),
    # threaded into every engine the scheme constructs so recurring
    # straggler patterns pay the symbolic solve once.  ``None`` with the
    # replay backend means concrete-mask decodes solve per call (still
    # bit-correct, just uncached); other backends ignore it.
    schedule_cache: object | None = None

    @classmethod
    def build(cls, code: LDPCCode, moments: Moments, *, lr: float, **kw) -> "Scheme2":
        return cls(code=code, C=encode_moment(code, moments.M), b=moments.b, lr=lr, **kw)

    @classmethod
    def build_seeded(cls, code: LDPCCode, moments: Moments, *, lr: float,
                     **kw) -> "Scheme2":
        """Scheme 2 over a seeded LDGM code with on-the-fly encode: stores
        ``M`` itself ((k, k) — the preprocessing output) instead of the
        ``(N, k)`` encoded ``C``, and regenerates each worker's generator
        row from the seed at every step (``z = gather(M θ)``); pass
        ``encode_fused=True`` to run that gather inside the fused Pallas
        encode kernel (no index tables at all)."""
        return cls(code=code, C=jnp.asarray(moments.M), b=moments.b, lr=lr,
                   seeded_encode=True, **kw)

    def _encode(self, y: jax.Array) -> jax.Array:
        """Seeded codeword of ``y`` ((K,) or (K, V)): fused kernel or
        table gather — bit-identical under jit."""
        if self.encode_fused:
            return encode_seeded(self.code, y)
        idx, coeff = generator_gather_tables(self.code)
        return gather_encode(idx, coeff, y)

    @property
    def w(self) -> int:
        return self.code.N

    @property
    def engine(self) -> CodedComputeEngine:
        return CodedComputeEngine(self.code, decode_iters=self.decode_iters,
                                  backend=self.decode_backend,
                                  adaptive=self.adaptive,
                                  schedule_cache=self.schedule_cache)

    def worker_mask_to_erasure(self, mask: jax.Array) -> jax.Array:
        return mask  # N == w: row j <-> worker j

    def _debias(self, g: jax.Array) -> jax.Array:
        if not self.debias:
            return g
        qD = density_evolution.q_final(
            self.q0_for_debias, self.code.l, self.code.r, self.decode_iters
        )
        return g / max(1.0 - qD, 1e-6)

    def finish_gradient(self, c_hat: jax.Array, unresolved: jax.Array):
        """Scheme-2 gradient epilogue from recovered systematic values:
        zero ``b̂`` on the unresolved set, subtract, (optionally) debias.

        Shapes: ``c_hat (K,)`` / ``unresolved (K,)`` or batched ``(B, K)``.
        Returns ``(gradient, unresolved_count)`` with the count reduced over
        the coordinate axis.  This is THE epilogue — :meth:`gradient`,
        :meth:`gradient_batch`, and the serving layer's continuous launches
        (:mod:`repro.serving.coded_queries`) all share it.
        """
        b = self.b if c_hat.ndim == 1 else self.b[None, :]
        b_hat = jnp.where(unresolved, 0.0, b)
        return self._debias(c_hat - b_hat), unresolved.sum(axis=-1)

    def gradient(self, theta: jax.Array, straggler_mask: jax.Array):
        """Return (approx gradient, |U_t|)."""
        if self.seeded_encode:
            z = self._encode(jnp.matmul(self.C, theta,
                                        precision=Precision.HIGHEST))
        else:
            # (N,) worker inner products (codeword of C)
            z = jnp.matmul(self.C, theta, precision=Precision.HIGHEST)
        erased = self.worker_mask_to_erasure(straggler_mask)
        c_hat, unresolved = self.engine.recover(z, erased)
        return self.finish_gradient(c_hat, unresolved)

    def gradient_batch(self, theta_B: jax.Array, straggler_mask_B: jax.Array):
        """B concurrent queries (θ_b, mask_b) → (B, k) gradients, ONE decode.

        Each query carries its own straggler realization; the worker-product
        matvecs fuse into one (B, k) @ (k, N) matmul and the B peeling
        decodes run as a single batched launch
        (:meth:`CodedComputeEngine.decode_batch`).  Per-query results match
        :meth:`gradient` run separately — including for ``adaptive=True``
        schemes, where each query's decode now early-exits at ITS OWN
        fixpoint (per-slot adaptive batch decode) instead of running the
        whole batch for the worst-case ``decode_iters`` budget.
        """
        if self.seeded_encode:
            Z = self._encode(jnp.matmul(
                theta_B, self.C.T, precision=Precision.HIGHEST).T).T  # (B, N)
        else:
            Z = jnp.matmul(theta_B, self.C.T,
                           precision=Precision.HIGHEST)  # (B, N)
        erased_B = jax.vmap(self.worker_mask_to_erasure)(straggler_mask_B)
        c_hat, unresolved = self.engine.recover_batch(Z, erased_B)
        return self.finish_gradient(c_hat, unresolved)

    def step(self, theta: jax.Array, straggler_mask: jax.Array) -> tuple[jax.Array, jax.Array]:
        g, n_unresolved = self.gradient(theta, straggler_mask)
        return self.projection(theta - self.lr * g), n_unresolved


@dataclasses.dataclass(frozen=True)
class Scheme1:
    """Exact-gradient coded PGD (paper Scheme 1): any linear code, exact
    recovery of M θ from the non-straggling rows via least squares.

    Exact as long as #stragglers < d_min (Proposition 1); with more
    stragglers the per-block least-squares solve is underdetermined and the
    recovered gradient degrades (the lstsq minimum-norm solution is used).
    """

    code: LDPCCode
    C_blocks: jax.Array  # (k/K, N, k)
    b: jax.Array
    lr: float
    projection: Callable[[jax.Array], jax.Array] = projections.identity

    @classmethod
    def build(cls, code: LDPCCode, moments: Moments, *, lr: float, **kw) -> "Scheme1":
        return cls(code=code, C_blocks=encode_moment_blocks(code, moments.M),
                   b=moments.b, lr=lr, **kw)

    @property
    def w(self) -> int:
        return self.code.N

    def gradient(self, theta: jax.Array, straggler_mask: jax.Array):
        G = jnp.asarray(self.code.G, theta.dtype)  # (N, K)
        # Worker j computes one inner product per block: Z[i, j] = <C[i, j], theta>.
        Z = jnp.einsum("bnk,k->bn", self.C_blocks, theta,
                       precision=Precision.HIGHEST)  # (k/K, N)
        avail = (~straggler_mask).astype(theta.dtype)
        # Weighted least squares that zeroes out straggler rows:
        Gw = G * avail[:, None]
        Zw = Z * avail[None, :]

        def solve(zb):
            sol, *_ = jnp.linalg.lstsq(Gw, zb)
            return sol  # (K,) = M_{P_i} theta

        Mtheta = jax.vmap(solve)(Zw).reshape(-1)  # (k,)
        return Mtheta - self.b, jnp.int32(0)

    def step(self, theta, straggler_mask):
        g, aux = self.gradient(theta, straggler_mask)
        return self.projection(theta - self.lr * g), aux


@dataclasses.dataclass(frozen=True)
class Scheme2Blocked:
    """Scheme 2 generalized to k > K (paper footnote 2): the k rows of M are
    partitioned into k/K blocks, each encoded with the SAME (N=w, K) code;
    worker j holds row j of every block (α = k/K rows) and returns α scalars.

    Because a straggler erases the same coordinate of EVERY block's codeword,
    all k/K codewords share one erasure pattern — the decode is one
    payload-batched peeling pass with payload width k/K (the engine's V
    axis, orthogonal to its B axis of independent patterns).  This is the
    configuration of the paper's experiments: a (40, 20) code with
    k ∈ {200, ..., 2000}.
    """

    code: LDPCCode
    C_blocks: jax.Array  # (k/K, N, k)
    b: jax.Array         # (k,)
    lr: float
    decode_iters: int = 10
    decode_backend: str = "auto"  # dense | sparse | pallas | auto (decoder.py)
    projection: Callable[[jax.Array], jax.Array] = projections.identity

    @classmethod
    def build(cls, code: LDPCCode, moments: Moments, *, lr: float, **kw):
        return cls(code=code, C_blocks=encode_moment_blocks(code, moments.M),
                   b=moments.b, lr=lr, **kw)

    @property
    def w(self) -> int:
        return self.code.N

    @property
    def engine(self) -> CodedComputeEngine:
        return CodedComputeEngine(self.code, decode_iters=self.decode_iters,
                                  backend=self.decode_backend)

    def gradient(self, theta: jax.Array, straggler_mask: jax.Array):
        eng = self.engine
        nb = self.C_blocks.shape[0]
        Z = jnp.einsum("bnk,k->nb", self.C_blocks, theta,
                       precision=Precision.HIGHEST)  # (N, k/K)
        dec = eng.decode(eng.erase(Z, straggler_mask), straggler_mask)
        g, unresolved_flat = blocked_epilogue(dec.values, dec.erased, self.b,
                                              K=self.code.K, nb=nb)
        return g, unresolved_flat.sum()

    def step(self, theta, straggler_mask):
        g, aux = self.gradient(theta, straggler_mask)
        return self.projection(theta - self.lr * g), aux


def run_pgd(
    scheme,
    theta0: jax.Array,
    straggler_model,
    steps: int,
    *,
    key: jax.Array | None = None,
    theta_star: jax.Array | None = None,
    loss_fn: Callable[[jax.Array], jax.Array] | None = None,
) -> RunResult:
    """Generic driver over any :class:`repro.core.schemes.Scheme`: sample a
    straggler mask, take a coded step, track error.

    Jit-compiled as a single ``lax.scan`` over steps — the whole optimization
    trajectory runs on-device.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    w = scheme.w

    def metric(theta):
        if theta_star is not None:
            return jnp.linalg.norm(theta - theta_star)
        if loss_fn is not None:
            return loss_fn(theta)
        return jnp.linalg.norm(theta)

    @jax.jit
    def scan_all(theta0, key):
        def body(carry, key_t):
            theta, tbar, t = carry
            mask = straggler_model.sample(key_t, w)
            theta2, unresolved = scheme.step(theta, mask)
            tbar2 = (tbar * t + theta2) / (t + 1.0)
            return (theta2, tbar2, t + 1.0), (metric(theta2), unresolved)

        keys = jax.random.split(key, steps)
        (theta, tbar, _), (errs, unres) = jax.lax.scan(
            body, (theta0, jnp.zeros_like(theta0), 0.0), keys
        )
        return theta, tbar, errs, unres

    theta, tbar, errs, unres = scan_all(theta0, key)
    return RunResult(theta, tbar, errs, unres)
