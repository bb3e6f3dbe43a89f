"""The sync driver of a gradient-aggregation configuration: one
``DistributedCodedAggregator.aggregate(partials, worker_mask)`` per call,
the two partial sets in alternation.  Each checked call's sum is compared
with :func:`bench.kinds.reference.shard_sum` over the shards the
reference's own peeling resolves.  Data and work counts are the kind's
(:mod:`bench.kinds.gradagg`).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.kinds import codes, gradagg, reference, seed_key

__all__ = ["Cell"]

# window calls whose sums are kept for the comparison are drawn from the
# seed among the first _KEEP_FROM of the window (the last call is kept too)
_KEEP_FROM = 16


class _ReferenceDriver:
    """The reference in the program's place (the control)."""

    def __init__(self, cell, precision: str):
        self.cell, self.precision = cell, precision

    def aggregate(self, partials, worker_mask):
        resolved, n_unres = self.cell.reference_resolution(worker_mask)
        return reference.shard_sum(partials, jnp.asarray(resolved),
                                   self.precision), n_unres


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, log,
                 control: str | None = None,
                 cache_dir=codes.CACHE_DIR) -> None:
        self.K, self.N, self.dim = cfg["K"], cfg["N"], cfg["dim"]
        self.rounds = cfg["decode_rounds"]
        self.n_workers = cfg["workers"]
        if self.n_workers != self.N:
            raise ValueError("gradagg runs one symbol per worker")

        t0 = time.perf_counter()
        self.partials = jax.block_until_ready(
            gradagg.make_partials(seed_key(seed), self.K, self.dim))
        log("data_s", time.perf_counter() - t0)

        t0 = time.perf_counter()
        code, hit, self.h_cols, self.h_abs = codes.load_code(
            self.K, cfg["l"], cfg["r"], cfg["code_seed"], cache_dir)
        if code.N != self.N:
            raise ValueError(f"code has N={code.N}, configuration {self.N}")
        self.G = code.G             # host float64 (N, K)
        log("code_s", time.perf_counter() - t0, cache="hit" if hit else
            "built")

        t0 = time.perf_counter()
        if control is None:
            from repro.core.grad_agg import CodedAggregator
            from repro.distributed.master import DistributedCodedAggregator
            from repro.distributed.topology import (WorkerTopology,
                                                    make_worker_mesh)
            agg = CodedAggregator(code, decode_iters=self.rounds,
                                  decode_backend=cfg["decode_backend"],
                                  debias_scale=cfg["debias_scale"])
            self.driver = DistributedCodedAggregator(
                agg, WorkerTopology(self.n_workers, self.N),
                make_worker_mesh(1))
        else:
            self.driver = _ReferenceDriver(self, control)
        log("encode_s", time.perf_counter() - t0)
        warm = traffic["warm_calls"]
        rng = np.random.default_rng([seed, 0x6B65])
        self._keep = set(range(warm)) | set((warm + rng.choice(
            _KEEP_FROM, traffic["checked_calls"], replace=False)).tolist())
        self.masks: list = []       # per call
        self.unresolved: list = []  # per call
        self.kept: dict = {}        # checked call -> its sum
        self.last = None            # (call, sum) of the latest call

    def call(self, i: int, worker_mask: np.ndarray) -> None:
        total, n_unres = self.driver.aggregate(self.partials[i % 2],
                                               worker_mask)
        total.block_until_ready()
        self.masks.append(worker_mask)
        self.unresolved.append(int(n_unres))
        if i in self._keep:
            self.kept[i] = total
        self.last = (i, total)

    def release(self) -> None:
        self.driver = None

    def reference_resolution(self, worker_mask) -> tuple:
        left = reference.peel_erased(self.h_cols, np.asarray(worker_mask,
                                                             bool),
                                     self.rounds)[:self.K]
        return ~left, int(left.sum())

    def compare(self) -> dict:
        """The numbers compared:

        * ``unresolved_gap``: Σ over EVERY call of |program's unresolved
          count − the reference's|;
        * ``sum_err``: the widest, over the warm calls, the window calls
          kept and the last call, of ‖sum − reference sum‖₂ over its
          float32 error bound: each received symbol's K-term product, the
          peel chain that recovered a shard (:func:`reference.peel_bound`
          on the symbols' 2-norms: the triangle inequality carries the
          elementwise bound over to norms), the sum of the shards and the
          reference's own sum.
        """
        u, K = reference.F32_OP_ERROR, self.K
        gap = sum(abs(n - self.reference_resolution(m)[1])
                  for m, n in zip(self.masks, self.unresolved))
        absG = np.abs(self.G)
        norms = []     # per partial set: (‖z_i‖ (N,), Σ_k|G_ik|‖p_k‖ (N,))
        for p in self.partials:
            gram = np.asarray(_gram(p), np.float64)
            z2 = np.einsum("ik,kl,il->i", self.G, gram, self.G)
            pk = np.sqrt(np.diag(gram))
            norms.append((np.sqrt(np.maximum(z2, 0.0)), absG @ pk, pk))
        worst = [0.0]
        for i, total in [*self.kept.items(), self.last]:
            resolved, _ = self.reference_resolution(self.masks[i])
            want = reference.shard_sum(self.partials[i % 2],
                                       jnp.asarray(resolved))
            zn, zin, pk = norms[i % 2]
            err = reference.peel_bound(
                self.h_cols, self.h_abs, np.asarray(self.masks[i], bool),
                zn, np.sqrt(K) * u * zin, self.rounds)[:K]
            bound = (err[resolved].sum()
                     + K * u * (zn[:K][resolved].sum() + pk.sum()))
            worst.append(float(jnp.linalg.norm(total - want)) / bound)
        # np.max keeps a NaN, which the harness reads as over the limit
        return {"unresolved_gap": gap, "sum_err": float(np.max(worst))}


@jax.jit
def _gram(p):
    """``P·Pᵀ`` of one ``(K, dim)`` partial set, float32 at HIGHEST."""
    return jnp.matmul(p, p.T, precision=jax.lax.Precision.HIGHEST)
