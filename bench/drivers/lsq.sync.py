"""The sync driver of a least-squares configuration: the window drives
``DistributedCodedGD.step(θ, worker_mask)`` from the iterate the previous
call returned, and each checked call's answer is compared with
:func:`bench.kinds.reference.gd_step` at the same input iterate.  Data
and work counts are the kind's (:mod:`bench.kinds.lsq`).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.kinds import codes, lsq, reference, seed_key

__all__ = ["Cell"]


class _ReferenceDriver:
    """The reference in the program's place (the control): the same
    ``step`` surface, computed by :func:`reference.gd_step`."""

    def __init__(self, cell, precision: str):
        self.cell, self.precision = cell, precision

    def step(self, theta, worker_mask):
        c = self.cell
        resolved, n_unres = c.reference_resolution(worker_mask)
        theta2 = reference.gd_step(c.M, c.b, c.lr, theta,
                                   jnp.asarray(resolved), self.precision)
        return theta2, n_unres, c.rounds, c.rounds


class Cell:
    """One run's system, data and record of answers."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, log,
                 control: str | None = None,
                 cache_dir=codes.CACHE_DIR) -> None:
        self.k, self.N = cfg["k"], cfg["N"]
        self.rounds = cfg["decode_rounds"]
        self.n_workers = cfg["workers"]
        self.rows_per_worker = self.N // self.n_workers

        t0 = time.perf_counter()
        M, b, lr = lsq.make_data(seed_key(seed), cfg["m"], self.k)
        self.M, self.b = M, b
        self.lr = float(lr)         # waits for the data: lr depends on M
        log("data_s", time.perf_counter() - t0)

        t0 = time.perf_counter()
        code, hit, self.h_cols, self.h_abs = codes.load_code(
            self.k, cfg["l"], cfg["r"], cfg["code_seed"], cache_dir)
        if code.N != self.N:
            raise ValueError(f"code has N={code.N}, configuration {self.N}")
        self.G = code.G             # host float64; the bound reads |G·M|
        log("code_s", time.perf_counter() - t0, cache="hit" if hit else
            "built")

        t0 = time.perf_counter()
        if control is None:
            from repro.core.coded_step import Scheme2
            from repro.core.encoding import encode_moment
            from repro.distributed.master import DistributedCodedGD
            from repro.distributed.topology import (WorkerTopology,
                                                    make_worker_mesh)
            scheme = Scheme2(code=code, C=encode_moment(code, M), b=b,
                             lr=self.lr, decode_iters=self.rounds,
                             decode_backend=cfg["decode_backend"])
            self.driver = DistributedCodedGD(
                scheme, WorkerTopology(self.n_workers, self.N),
                make_worker_mesh(1), worker_encode=cfg["worker_encode"])
            jax.block_until_ready(scheme.C)
        else:
            self.driver = _ReferenceDriver(self, control)
        log("encode_s", time.perf_counter() - t0)
        self.theta = jnp.zeros(self.k, jnp.float32)
        # per call: (worker mask, θ in, θ out, unresolved count)
        self.trail: list = []
        self._warm = traffic["warm_calls"]
        self._n_checked = traffic["checked_calls"]
        self._rng = np.random.default_rng([seed, 0x6B65])

    # ------------------------------------------------------------ window

    def call(self, i: int, worker_mask: np.ndarray) -> None:
        theta2, n_unres, _, _ = self.driver.step(self.theta, worker_mask)
        theta2.block_until_ready()
        self.trail.append((worker_mask, self.theta, theta2, int(n_unres)))
        self.theta = theta2

    def release(self) -> None:
        """Drop the system under test (its encoded operator, tables and
        programs); the data and the answers stay for the comparison."""
        self.driver = None

    # --------------------------------------------------------- reference

    def reference_resolution(self, worker_mask) -> tuple:
        """``(resolved systematic coordinates (k,), unresolved count)``
        for a worker mask, by the reference's own peeling."""
        erased = np.repeat(np.asarray(worker_mask, bool),
                           self.rows_per_worker)
        left = reference.peel_erased(self.h_cols, erased, self.rounds)[
            :self.k]
        return ~left, int(left.sum())

    def checked_calls(self) -> list:
        """The warm calls (the first steps, from θ = 0) and a sample of
        the window's calls drawn from the seed."""
        window = np.arange(self._warm, len(self.trail))
        pick = self._rng.choice(window, min(self._n_checked, window.size),
                                replace=False)
        return list(range(self._warm)) + sorted(pick.tolist())

    def compare(self) -> dict:
        """The numbers compared over :meth:`checked_calls`:

        * ``unresolved_gap``: Σ |program's unresolved count − the
          reference's|;
        * ``err_over_bound``: the widest gap of θ' to the reference's,
          each coordinate in units of its float32 error bound: the
          worker's k-term product, the peel chain that recovered it
          (:func:`reference.peel_bound`), the gradient's subtraction, the
          update, and the reference's own product.  A coordinate left
          unresolved has bound 0: it must not move at all.
        """
        u, k = reference.F32_OP_ERROR, self.k
        G = jnp.asarray(self.G, jnp.float32)
        absC = _abs_product(G, self.M)
        nnz = np.count_nonzero(self.G, axis=1)
        b = np.asarray(self.b, np.float64)
        gap, worst = 0, [0.0]
        for i in self.checked_calls():
            mask, theta_in, theta_out, n_unres = self.trail[i]
            resolved, n_ref = self.reference_resolution(mask)
            gap += abs(n_unres - n_ref)
            want = reference.gd_step(self.M, self.b, self.lr, theta_in,
                                     jnp.asarray(resolved))
            y, absMth, z, zC, zG = (np.asarray(a, np.float64) for a in
                                    _magnitudes(self.M, G, absC, theta_in))
            erased = np.repeat(np.asarray(mask, bool), self.rows_per_worker)
            z_err = np.sqrt(k) * u * zC + np.sqrt(nnz + 1) * u * zG
            bound = reference.peel_bound(self.h_cols, self.h_abs, erased,
                                         np.abs(z), z_err,
                                         self.rounds)[:k]
            g = y - b
            th_ref = np.asarray(want, np.float64)
            step = self.lr * np.abs(np.where(resolved, g, 0.0))
            # unresolved coordinates: tolerance 0, they must not move
            tol = np.where(
                resolved,
                self.lr * (np.where(resolved, bound, 0.0) + u * np.abs(g)
                           + np.sqrt(k) * u * absMth)
                + 2 * u * (step + np.abs(th_ref)),
                0.0)
            dev = np.abs(np.asarray(theta_out, np.float64) - th_ref)
            worst.append((dev / np.maximum(tol, 1e-300)).max())
        # np.max keeps a NaN, which the harness reads as over the limit
        return {"unresolved_gap": gap,
                "err_over_bound": float(np.max(worst))}


@jax.jit
def _abs_product(G, M):
    """``|G·M|``: the encoded operator's magnitudes, as the reference
    computes them (the worker's product errs by a share of ``|C|·|θ|``)."""
    return jnp.abs(jnp.matmul(G, M, precision=jax.lax.Precision.HIGHEST))


@jax.jit
def _magnitudes(M, G, absC, theta):
    """``(Mθ, |M|·|θ|, G·Mθ, |C|·|θ|, |G|·|M|·|θ|)``, all float32 at
    HIGHEST, for one checked step's bound."""
    hi = jax.lax.Precision.HIGHEST
    y = jnp.matmul(M, theta, precision=hi)
    a = jnp.abs(theta)
    absMth = jnp.matmul(jnp.abs(M), a, precision=hi)
    return (y, absMth, jnp.matmul(G, y, precision=hi),
            jnp.matmul(absC, a, precision=hi),
            jnp.matmul(jnp.abs(G), absMth, precision=hi))
