"""The pipelined driver of a least-squares configuration: one call is a
chunk of ``chunk`` consecutive steps of
:meth:`repro.distributed.pipeline.AsyncDistributedCodedGD.run` (depth,
fold window and staleness decay from the traffic file), under the call's
table of worker delays, from the iterate the previous chunk ended at.
The driver's own telemetry picks each step's cut; its estimators carry
over from chunk to chunk.  The step is the configuration's ``lr`` times
the traffic's ``lr_scale``: gradients a step stale make GD stable only
for ``lr·λ_max < 1``, and ``lr = 1/λ̂`` from power iteration sits just
past that edge (``λ̂`` is 0.7–1% low at k = 512–2048), so the sync step
size diverges in a pipelined run of a few thousand steps.

Each checked step is compared with :mod:`bench.kinds.pipe_reference` at
the program's own iterates: the step's cut (from its delays and the
``wait_for`` the driver reports), the workers' stale iterate, the source
steps' re-decoded survivors and their staleness weights.  Data and work
counts are the kind's (:mod:`bench.kinds.lsq`); the error bound per
coordinate is the sync driver's (:mod:`drivers/lsq.sync.py`).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import load_part
from bench.kinds import codes, lsq, pipe_reference, reference, seed_key

__all__ = ["Cell"]

_sync = load_part("drivers", "lsq.sync")
# steps of a checked chunk compared with the reference: the first two (the
# workers still at the chunk's first iterate) and this many drawn from
# the seed
_STEPS_DRAWN = 16
# the workers the control waits for, of W (it has no telemetry)
_CONTROL_WAIT = 0.75


class _Run:
    """What one call produced: ``thetas (T + 1, k)`` host iterates (the
    chunk's input first), and per step the cut waited for, the fold
    window and the unresolved count after late folds."""

    def __init__(self, thetas, wait_for, windows, unresolved):
        self.thetas, self.wait_for = thetas, wait_for
        self.windows, self.unresolved = windows, unresolved


class _ReferencePipe:
    """The reference in the program's place (the control): each step of
    the chunk from its own previous iterates at ``precision``."""

    def __init__(self, cell, precision: str):
        self.cell, self.precision = cell, precision

    def run(self, theta, delays) -> _Run:
        c = self.cell
        T, W = delays.shape
        wait = np.full(T, int(_CONTROL_WAIT * W))
        windows = np.full(T, c.window)
        plan = c.plan(delays, wait, windows)
        X = [np.asarray(theta)]
        for t in range(T):
            X.append(np.asarray(c.answer(X, t, plan, self.precision)[0]))
        return _Run(np.stack(X), wait, windows, plan.unresolved)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, log,
                 control: str | None = None,
                 cache_dir=codes.CACHE_DIR) -> None:
        self.k, self.N = cfg["k"], cfg["N"]
        self.rounds = cfg["decode_rounds"]
        self.n_workers = cfg["workers"]
        self.rows_per_worker = self.N // self.n_workers
        pipe = traffic["pipeline"]
        self.depth, self.window = pipe["depth"], pipe["max_staleness"]
        self.decay = pipe["staleness_decay"]
        if self.depth != 2:
            raise ValueError("the reference reads a depth-2 pipeline")

        t0 = time.perf_counter()
        M, b, lr = lsq.make_data(seed_key(seed), cfg["m"], self.k)
        self.M, self.b = M, b
        self.lr = float(lr) * pipe["lr_scale"]
        log("data_s", time.perf_counter() - t0)

        t0 = time.perf_counter()
        code, hit, self.h_cols, self.h_abs = codes.load_code(
            self.k, cfg["l"], cfg["r"], cfg["code_seed"], cache_dir)
        self.G = code.G
        log("code_s", time.perf_counter() - t0, cache="hit" if hit else
            "built")

        t0 = time.perf_counter()
        if control is None:
            from repro.core.coded_step import Scheme2
            from repro.core.encoding import encode_moment
            from repro.distributed.pipeline import AsyncDistributedCodedGD
            from repro.distributed.topology import (WorkerTopology,
                                                    make_worker_mesh)
            scheme = Scheme2(code=code, C=encode_moment(code, M), b=b,
                             lr=self.lr, decode_iters=self.rounds,
                             decode_backend=cfg["decode_backend"])
            self.driver = AsyncDistributedCodedGD(
                scheme, WorkerTopology(self.n_workers, self.N),
                make_worker_mesh(1), depth=self.depth,
                max_staleness=self.window, staleness_decay=self.decay,
                worker_encode=cfg["worker_encode"])
            self.fold_rounds = self.driver.max_rounds
            jax.block_until_ready(scheme.C)
        else:
            self.driver = _ReferencePipe(self, control)
            self.fold_rounds = self.rounds
        log("encode_s", time.perf_counter() - t0)
        self.theta = jnp.zeros(self.k, jnp.float32)
        self.calls: list = []       # per call: (delays, _Run)
        self._warm = traffic["warm_calls"]
        self._n_checked = traffic["checked_calls"]
        self._rng = np.random.default_rng([seed, 0x7069])

    # ------------------------------------------------------------ window

    def call(self, i: int, delays: np.ndarray) -> None:
        if isinstance(self.driver, _ReferencePipe):
            run = self.driver.run(self.theta, delays)
            self.theta = jnp.asarray(run.thetas[-1])
        else:
            from repro.core.straggler import BernoulliStragglers, \
                ScheduledDelays

            res = self.driver.run(
                self.theta, BernoulliStragglers(0.0), delays.shape[0],
                key=jax.random.PRNGKey(i),
                delay_model=ScheduledDelays.build(delays),
                record_thetas=True)
            res.theta.block_until_ready()
            run = _Run(np.concatenate([np.asarray(self.theta)[None],
                                       res.thetas]),
                       res.wait_for, res.staleness, res.unresolved)
            self.theta = res.theta
        self.calls.append((delays, run))

    def release(self) -> None:
        """Drop the system under test; the data and the answers stay."""
        self.driver = None

    # --------------------------------------------------------- reference

    def plan(self, delays, wait, windows) -> pipe_reference.FoldPlan:
        cut, lags = pipe_reference.cuts(delays, wait)
        return pipe_reference.plan_chunk(
            cut, lags, np.asarray(windows), self.h_cols, self.k,
            self.rows_per_worker, self.rounds, self.fold_rounds)

    def answer(self, X, t: int, plan, precision: str = "highest"):
        """``(θ_{t+1}, parts)`` of the reference from the iterates ``X``
        (``X[0]`` the chunk's input): ``parts`` lists ``(coords, weight,
        worker iterate, erased workers, rounds)`` of the step's own decode
        and of each fold landing at it."""
        def at(s):
            return X[max(s - 1, 0)]

        parts = [(plan.main[t], 1.0, at(t), plan.cut[t], self.rounds)]
        parts += [(newly, float(np.float32(self.decay ** lag)), at(s),
                   remaining, self.fold_rounds)
                  for s, lag, newly, remaining in plan.folds[t]]
        g = jnp.zeros(self.k, jnp.float32)
        for coords, w, theta_w, _, _ in parts:
            y = reference._matmul(self.M, jnp.asarray(theta_w), precision)
            g = g + w * jnp.where(jnp.asarray(coords), y - self.b, 0.0)
        return jnp.asarray(X[t]) - self.lr * g, parts

    def checked(self) -> list:
        """``(call, step)`` pairs compared: of the warm calls and a
        seed-drawn sample of the window's, the first two steps and a
        seed-drawn sample of the rest."""
        window = np.arange(self._warm, len(self.calls))
        pick = self._rng.choice(window, min(self._n_checked, window.size),
                                replace=False)
        out = []
        for i in list(range(min(self._warm, len(self.calls)))) + sorted(
                pick.tolist()):
            T = self.calls[i][0].shape[0]
            steps = {0, 1} | set(self._rng.choice(
                T, min(_STEPS_DRAWN, T), replace=False).tolist())
            out += [(i, t) for t in sorted(steps) if t < T]
        return out

    def compare(self) -> dict:
        """The numbers compared:

        * ``unresolved_gap``: Σ over EVERY step of every call of |program's
          unresolved count after late folds − the reference's|;
        * ``err_over_bound``: over :meth:`checked`, the widest gap of
          θ_{t+1} to the reference's answer from the program's own
          iterates, each coordinate in units of its float32 error bound:
          for the step's own decode and each fold, the worker's k-term
          product, the peel chain (:func:`reference.peel_bound` under that
          decode's erasures), the subtraction and the weight; the update;
          the reference's own products.  A coordinate no decode of the step
          resolved has bound 0: it must not move.
        """
        u, k = reference.F32_OP_ERROR, self.k
        G = jnp.asarray(self.G, jnp.float32)
        absC = _sync._abs_product(G, self.M)
        nnz = np.count_nonzero(self.G, axis=1)
        b = np.asarray(self.b, np.float64)
        plans = []
        gap = 0
        for delays, run in self.calls:
            plan = self.plan(delays, run.wait_for, run.windows)
            plans.append(plan)
            gap += int(np.abs(np.asarray(run.unresolved)
                              - plan.unresolved).sum())
        worst = [0.0]
        for i, t in self.checked():
            run, plan = self.calls[i][1], plans[i]
            want, parts = self.answer(run.thetas, t, plan)
            want = np.asarray(want, np.float64)
            theta_t = np.asarray(run.thetas[t], np.float64)
            tol = np.zeros(k)
            step = np.zeros(k)
            moved = np.zeros(k, bool)
            for coords, w, theta_w, erased_w, rounds in parts:
                y, absMth, z, zC, zG = (np.asarray(a, np.float64) for a in
                                        _sync._magnitudes(self.M, G, absC,
                                                          theta_w))
                erased = np.repeat(erased_w, self.rows_per_worker)
                z_err = np.sqrt(k) * u * zC + np.sqrt(nnz + 1) * u * zG
                bound = reference.peel_bound(self.h_cols, self.h_abs, erased,
                                             np.abs(z), z_err, rounds)[:k]
                g = np.where(coords, y - b, 0.0)
                tol += np.where(coords, w * (
                    np.where(coords, bound, 0.0) + 2 * u * np.abs(g)
                    + np.sqrt(k) * u * absMth), 0.0)
                step += w * np.abs(g)
                moved |= coords
            tol = np.where(moved, self.lr * tol
                           + 2 * u * (self.lr * step + np.abs(want)
                                      + np.abs(theta_t)), 0.0)
            dev = np.abs(np.asarray(run.thetas[t + 1], np.float64) - want)
            worst.append((dev / np.maximum(tol, 1e-300)).max())
        # np.max keeps a NaN, which the harness reads as over the limit
        return {"unresolved_gap": gap,
                "err_over_bound": float(np.max(worst))}
