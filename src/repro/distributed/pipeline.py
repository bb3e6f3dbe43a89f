"""Pipelined master/worker runtime: overlap worker matvec with master
decode, fold late stragglers into later updates with staleness weights.

The synchronous :class:`repro.distributed.master.DistributedCodedGD` runs
encode → wait → decode → update as a strict barrier per step, so worker
latency and master decode time ADD, and every worker slower than the
wait-for cutoff is erased outright.  This module relaxes both, keeping the
synchronous driver as the bit-parity reference:

**Double-buffered θ broadcast (depth-k pipeline).**  With ``depth = k``,
step ``t``'s worker launch computes its partial products at
``θ_{t-depth+1}`` — the newest iterate whose decode has certainly been
DISPATCHED by then — so the SPMD worker program of step ``t+1`` and the
master decode program of step ``t`` are independent device programs in
flight together (classic delayed-gradient SGD; "Stochastic Gradient
Coding", Bitar et al., arXiv:1905.05383, gives the convergence frame: a
stale gradient is a bounded-bias oracle, the paper's erasure model is the
zero-staleness limit).  ``depth = 1`` is the synchronous dependency chain
and stays BIT-IDENTICAL to ``DistributedCodedGD`` (``selfcheck
--pipeline``).  The host never calls ``block_until_ready`` on the critical
path: a bounded deque holds at most ``depth`` steps' un-pulled scalars and
JAX async dispatch keeps both device programs queued.

**Device-resident carried state.**  θ and the running average live on the
master device and thread through the fused master program (θ̄ with
``donate_argnums``; θ's output buffer doubles zero-copy as the master
shard of the replicated broadcast) — the per-step cost is ONE replicated
broadcast of the new iterate, not the synchronous path's put-per-operand
churn.  The convergence metric and the running average are computed INSIDE
the master program (θ* rides along as a traced operand), so a driver step
is exactly two device programs plus one broadcast.

**Late-arrival folding.**  Under a delay model, a worker slower than the
cutoff but landing within ``max_staleness`` later steps is not erased
forever: its partial products (computed at the stale θ it was given) are
re-decoded against the stored survivor vector of its source step, and the
NEWLY resolved coordinates enter the current update as a staleness-weighted
delta ``w(τ) · debias(ĉ′ − b)`` (``w(τ) = staleness_decay^τ``).  The fold
re-decode depends only on the source step's stored ``(z, mask)`` — not on
the current θ — so it pipelines like everything else.
``staleness_decay = 0`` (w ≡ 0) reproduces today's drop semantics exactly.
:class:`repro.distributed.telemetry.ArrivalLagEstimator` learns where late
arrivals land and :func:`repro.distributed.telemetry.pick_wait_and_staleness`
chooses ``(wait_for, max_staleness)`` online (``auto_staleness=True``).

:func:`pipeline_timeline` composes the simulated wall-clock of a depth-k
run from the injected worker delays and per-step decode service times —
the same simulated clock :class:`DistributedRunResult` has always recorded
(``step_times`` = the wait-for order statistic), extended to count master
decode time and pipeline overlap.  The benchmark's ``pipeline`` section
gates the sync/pipelined steps-per-second ratio on that clock, alongside
the measured host wall-clock ratio.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.coded_step import Scheme2
from repro.core.straggler import DelayModel
from repro.distributed.master import (
    DistributedCodedGD,
    _record_plan_metrics,
    _record_step_metrics,
    delay_step_control,
)
from repro.distributed.telemetry import (
    ArrivalLagEstimator,
    StragglerRateEstimator,
    decode_budget,
    pick_wait_and_staleness,
    pick_wait_for_cached,
)
from repro.distributed.topology import WorkerTopology
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.obs.trace import span as _span

__all__ = ["AsyncDistributedCodedGD", "PipelineRunResult",
           "pipeline_timeline"]


class PipelineRunResult(NamedTuple):
    theta: jax.Array         # final iterate
    theta_bar: jax.Array     # running average (folded into the master program)
    errors: np.ndarray       # (T,) ||θ_t - θ*|| (or loss / norm)
    unresolved: np.ndarray   # (T,) |U_t| per step AFTER late folds landed
    resolved_late: np.ndarray  # (T,) coords recovered by folds, per SOURCE step
    rounds: np.ndarray       # (T,) main-decode rounds spent per step
    fold_rounds: np.ndarray  # (T,) fold-decode rounds spent per step
    budgets: np.ndarray      # (T,) round budget granted per step
    rates: np.ndarray        # (T,) telemetry estimate q̂ entering each step
    wait_for: np.ndarray     # (T,) workers waited for (delay runs; else W)
    staleness: np.ndarray    # (T,) fold window in force per step
    step_times: np.ndarray   # (T,) simulated wait at the cutoff (delay runs)
    thetas: np.ndarray | None  # (T, K) per-step iterates (record_thetas=True)


def pipeline_timeline(waits, decode_times, depth: int
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Simulated wall-clock of a depth-k pipelined run.

    ``waits[t]`` is step ``t``'s worker phase (the injected wait-for order
    statistic), ``decode_times[t]`` its master phase (decode service,
    including any folds dispatched that step).  Worker launch ``t`` needs
    ``θ_{t-depth+1}``, i.e. the master phase of step ``t - depth + 1`` to
    have finished, and the worker fleet / the master each run one phase at
    a time — the classic two-stage pipeline recurrence:

      worker_end[t] = max(worker_end[t-1], master_end[t-depth]) + waits[t]
      master_end[t] = max(master_end[t-1], worker_end[t]) + decode_times[t]

    ``depth = 1`` degenerates to the synchronous barrier (total =
    Σ waits + Σ decode_times); larger depths hide the shorter phase behind
    the longer one.  Returns ``(worker_end, master_end)`` as (T,) arrays;
    ``master_end[-1]`` is the run's makespan.
    """
    if depth < 1:
        raise ValueError(f"depth must be >= 1; got {depth}")
    waits = np.asarray(waits, float)
    decode_times = np.asarray(decode_times, float)
    T = waits.shape[0]
    w_end = np.zeros(T)
    m_end = np.zeros(T)
    for t in range(T):
        theta_ready = m_end[t - depth] if t - depth >= 0 else 0.0
        start = max(w_end[t - 1] if t else 0.0, theta_ready)
        w_end[t] = start + waits[t]
        m_end[t] = max(m_end[t - 1] if t else 0.0, w_end[t]) + decode_times[t]
    return w_end, m_end


@dataclasses.dataclass
class _FoldEntry:
    """Stored survivors of one step, waiting for late arrivals to land."""
    step: int
    z_m: jax.Array           # (N,) master-device view of the worker output
    u: jax.Array             # (K,) unresolved mask on the master (updated)
    cut_mask: np.ndarray     # (W,) workers missed at the cutoff
    lags: np.ndarray         # (W,) arrival lags in step units
    window: int              # fold window in force at the source step


@dataclasses.dataclass
class _StepPlan:
    """One step's control-plane decision, fixed before any device work.

    This is the pipeline's per-step control record (formerly an internal
    ``ctrl`` dict): everything the host decided — the wait-for cut, the
    fold window, the decode budget, the telemetry estimate it acted on —
    lives here, is recorded into the obs registry at PLAN time (it is all
    host data; nothing waits on a device), and is reported back through
    :class:`PipelineRunResult`'s tail arrays.
    """
    cut: np.ndarray            # (W,) workers missed at the wait-for cutoff
    never: np.ndarray          # (W,) rows zeroed outright (outside window)
    lags: np.ndarray | None    # (W,) arrival lags (delay runs)
    wait: int                  # workers waited for
    window: int                # fold window in force
    budget: int                # decode round budget granted
    rate: float                # telemetry estimate q̂ ENTERING the step
    cutoff: float              # simulated wall-clock at the cutoff
    observed: float | None     # realized straggler fraction (telemetry obs)

    def record(self) -> None:
        """Feed the plan into the obs registry (host data only)."""
        _record_plan_metrics("pipeline", wait_for=self.wait, rate=self.rate,
                             observed=self.observed)
        reg = _obs_metrics.active()
        if reg is None:
            return
        reg.histogram("pipeline.staleness_window",
                      bins=_obs_metrics.LAG_BINS).observe(self.window)
        if self.lags is not None:
            reg.histogram("pipeline.arrival_lag",
                          bins=_obs_metrics.LAG_BINS).observe_many(
                              self.lags[self.cut])


@dataclasses.dataclass
class AsyncDistributedCodedGD:
    """Depth-k pipelined moment-encoded GD over a worker mesh.

    Wraps the synchronous :class:`DistributedCodedGD` (which supplies the
    worker program, the sharded operator placement, and the bit-parity
    reference) and replaces its barrier driver with the pipelined one
    described in the module docstring.  ``depth=1, max_staleness=0`` is
    bit-identical to ``DistributedCodedGD.run``.
    """

    scheme: Scheme2
    topology: WorkerTopology
    mesh: Mesh | None = None
    depth: int = 2
    # Fold window: how many steps a cut-off worker's partials stay foldable
    # (0 = drop semantics).  With auto_staleness=True this is the CAP the
    # online (wait_for, staleness) policy picks within.
    max_staleness: int = 0
    # w(τ) = staleness_decay ** τ for a fold landing τ steps late.  0.0
    # short-circuits every fold (w ≡ 0 ≡ drop semantics, bit-exactly).
    staleness_decay: float = 0.5
    auto_staleness: bool = False
    budget_mode: str = "fixed"
    worker_encode: str = "materialized"
    # "single" (default) or "replay": which decode the fused master program
    # runs.  "replay" pre-solves each step's peeling schedule HOST-SIDE in
    # the plan loop (the step-t mask is known before any device work, so
    # the symbolic solve never sits on the decode critical path) and the
    # per-step decode is the straight-line numeric replay — bit-identical
    # to "single" over a sparse engine.  Passed through to the wrapped
    # synchronous driver so the depth-1 parity reference runs the SAME
    # decode and shares the SAME schedule cache.
    master_decode: str = "single"
    estimator: StragglerRateEstimator | None = None
    lag_estimator: ArrivalLagEstimator | None = None
    max_rounds: int | None = None
    straggler_factor: float = 2.0
    schedule_cache: object | None = None

    def __post_init__(self) -> None:
        if self.depth < 1:
            raise ValueError(f"depth must be >= 1; got {self.depth}")
        if self.master_decode not in ("single", "replay"):
            raise ValueError(
                f"unknown pipeline master_decode {self.master_decode!r}; "
                "want 'single' or 'replay' (the sharded decode has no "
                "pipelined master program)")
        if self.max_staleness < 0:
            raise ValueError(
                f"max_staleness must be >= 0; got {self.max_staleness}")
        if not 0.0 <= self.staleness_decay <= 1.0:
            raise ValueError(f"staleness_decay must be in [0, 1]; "
                             f"got {self.staleness_decay}")
        if self.auto_staleness and self.max_staleness < 1:
            raise ValueError("auto_staleness picks the fold window within "
                             "max_staleness — set max_staleness >= 1")
        # The synchronous runtime supplies worker program + placement (and
        # stays available as the parity reference).  The pipelined master
        # program replaces its per-step master launch.
        self._sync = DistributedCodedGD(
            self.scheme, self.topology, self.mesh,
            budget_mode=self.budget_mode, worker_encode=self.worker_encode,
            master_decode=self.master_decode,
            estimator=self.estimator, max_rounds=self.max_rounds,
            straggler_factor=self.straggler_factor,
            schedule_cache=self.schedule_cache)
        self.mesh = self._sync.mesh
        self.estimator = self._sync.estimator
        self.schedule_cache = self._sync.schedule_cache
        if self.lag_estimator is None:
            self.lag_estimator = ArrivalLagEstimator()
        self.max_rounds = self._sync.max_rounds
        self.master_device = self._sync.master_device
        self._replicated = self._sync._replicated
        self._master_cache: dict = {}
        self._fold_program = self._build_fold_program()
        self._add = jax.jit(jnp.add)

    # ------------------------------------------------------------- programs

    @property
    def n_workers(self) -> int:
        return self.topology.n_workers

    def _build_master_program(self, *, with_folds: bool, loss_fn=None):
        """The fused per-step master program: decode + epilogue + update +
        running average + metric, one launch.  ``with_folds`` statically
        adds the fold-delta operand; the no-fold variant keeps the update
        arithmetic LITERALLY the synchronous program's (the depth-1 parity
        gate compares bits).

        Only θ̄ is donated: the θ output's buffer doubles as the master
        device's shard of the replicated broadcast (``device_put`` to the
        replicated sharding reuses the matching-device buffer zero-copy),
        so donating θ would delete the broadcast under the in-flight
        worker programs.
        """
        scheme, topo = self.scheme, self.topology
        eng = scheme.engine
        fixed = self.budget_mode == "fixed"

        if self.master_decode == "replay":
            # Replay variant: the decode dispatch stays EAGER (the mask is
            # concrete host data, the schedule is a cache hit — pre-solved
            # in the plan loop — and the replay executors jit internally
            # keyed on segment shapes); the value-level epilogue/update/
            # average/metric is ONE jitted program whose elementwise chain
            # is the same arithmetic as the fused variant below, so the
            # depth-1 parity gate against the sync replay driver holds.
            from repro.core.decoder import DecodeResult
            r_eng = dataclasses.replace(eng, backend="replay",
                                        schedule_cache=self.schedule_cache)

            def replay_epilogue(values, erased, theta, tbar, fold_dg, t,
                                theta_star):
                c_hat, unresolved = eng.systematic(
                    DecodeResult(values, erased, jnp.int32(0)))
                g, n_unres = scheme.finish_gradient(c_hat, unresolved)
                if with_folds:
                    g = g + fold_dg
                theta2 = scheme.projection(theta - scheme.lr * g)
                tbar2 = (tbar * t + theta2) / (t + 1.0)
                if loss_fn is None:
                    err = jnp.linalg.norm(theta2 - theta_star)
                else:
                    err = loss_fn(theta2)
                return theta2, tbar2, n_unres, err, unresolved

            epilogue = jax.jit(replay_epilogue, donate_argnums=(3,))

            def replay_master(z, worker_mask, theta, tbar, fold_dg, t,
                              budget, theta_star, H):
                del H             # replay reads its schedule, never H
                erased = topo.to_symbol_erasure(jnp.asarray(worker_mask))
                z = r_eng.erase(z, erased)
                if fixed:
                    dec = r_eng.decode(z, erased)
                    values, er2, rounds = (dec.values, dec.erased,
                                           dec.rounds_used)
                else:
                    dec = r_eng.decode_batch(z[None], erased[None],
                                             adaptive=True, budgets=budget)
                    values, er2, rounds = (dec.values[0], dec.erased[0],
                                           dec.rounds_used[0])
                theta2, tbar2, n_unres, err, u = epilogue(
                    values, er2, theta, tbar, fold_dg, t, theta_star)
                return theta2, tbar2, n_unres, rounds, err, u

            replay_master._cache_size = epilogue._cache_size
            return replay_master

        def master_program(z, worker_mask, theta, tbar, fold_dg, t, budget,
                           theta_star, H):
            eng_h = dataclasses.replace(eng, H=H)
            erased = topo.to_symbol_erasure(worker_mask)
            if fixed:
                c_hat, unresolved = eng_h.recover(z, erased)
                rounds = jnp.int32(eng.decode_iters)
            else:
                dec = eng_h.decode_batch(z[None], erased[None],
                                         adaptive=True, budgets=budget)
                c_hat, unresolved = eng.systematic(dec)
                c_hat, unresolved = c_hat[0], unresolved[0]
                rounds = dec.rounds_used[0]
            g, n_unres = scheme.finish_gradient(c_hat, unresolved)
            if with_folds:
                g = g + fold_dg
            theta2 = scheme.projection(theta - scheme.lr * g)
            tbar2 = (tbar * t + theta2) / (t + 1.0)
            if loss_fn is None:
                err = jnp.linalg.norm(theta2 - theta_star)
            else:
                err = loss_fn(theta2)
            return theta2, tbar2, n_unres, rounds, err, unresolved

        return jax.jit(master_program, donate_argnums=(3,))

    def _get_master_program(self, *, with_folds: bool, loss_fn=None):
        key = (with_folds, id(loss_fn) if loss_fn is not None else None)
        if key not in self._master_cache:
            self._master_cache[key] = self._build_master_program(
                with_folds=with_folds, loss_fn=loss_fn)
        return self._master_cache[key]

    def _build_fold_program(self):
        """Re-decode a stored step's survivors with the newly-landed rows
        restored; the staleness-weighted delta covers exactly the
        coordinates the original decode left unresolved (never resolved
        coords — those were already applied — so nothing double-counts).
        Budget is a traced operand (adaptive decode at B=1): a fold that
        has little left to peel exits early, and changing budgets/weights
        never recompile."""
        scheme, topo = self.scheme, self.topology
        eng = scheme.engine

        def fold_program(z, remaining_mask, u_old, budget, w, H):
            erased = topo.to_symbol_erasure(remaining_mask)
            dec = dataclasses.replace(eng, H=H).decode_batch(
                eng.erase(z, erased)[None], erased[None], adaptive=True,
                budgets=budget)
            c2, u2 = eng.systematic(dec)
            c2, u2 = c2[0], u2[0]
            newly = u_old & ~u2
            delta = scheme._debias(jnp.where(newly, c2 - scheme.b, 0.0)) * w
            return delta, u_old & u2, newly.sum(), dec.rounds_used[0]

        return jax.jit(fold_program)

    def _cache_size(self) -> int:
        """Compiled-variant count across the pipelined programs (the
        no-recompile tests pin this to one per program in use)."""
        sizes = [p._cache_size() for p in self._master_cache.values()]
        return max(sizes + [0]) if sizes else 0

    # -------------------------------------------------------------- driving

    def run(
        self,
        theta0: jax.Array,
        straggler_model,
        steps: int,
        *,
        key: jax.Array | None = None,
        theta_star: jax.Array | None = None,
        loss_fn: Callable[[jax.Array], jax.Array] | None = None,
        delay_model: DelayModel | None = None,
        record_thetas: bool = False,
    ) -> PipelineRunResult:
        """Drive ``steps`` pipelined master steps.

        Mirrors :meth:`DistributedCodedGD.run`'s surface (same key
        schedule, same straggler/delay models, same telemetry policy —
        shared through :func:`repro.distributed.master.delay_step_control`
        so both runtimes realize identical masks).  Folding needs arrival
        lags, so it activates only under a ``delay_model``.
        """
        if key is None:
            key = jax.random.PRNGKey(0)
        keys = jax.random.split(key, steps)
        W = self.n_workers
        code = self.scheme.code
        sync = self._sync
        est = self.estimator
        tau = self.depth - 1

        # ---- control plane, presampled host-side (one pass, no per-step
        # device round-trips in the pipelined loop) ----------------------
        if delay_model is not None:
            delays_all = np.stack([
                np.asarray(delay_model.sample_delays(keys[t], W))
                for t in range(steps)])
        else:
            masks_all = np.stack([
                np.asarray(straggler_model.sample(keys[t], W))
                for t in range(steps)])

        ctrl: list[_StepPlan] = []
        for t in range(steps):
            if delay_model is not None:
                if self.auto_staleness:
                    wait, window = pick_wait_and_staleness(
                        est.rate, self.lag_estimator, W, code.l, code.r,
                        max_window=self.max_staleness)
                else:
                    wait = pick_wait_for_cached(est.rate, W, code.l, code.r)
                    window = self.max_staleness
                cut, cutoff, observed = delay_step_control(
                    delays_all[t], wait, self.straggler_factor)
                lags = DelayModel.arrival_lags(delays_all[t], cutoff)
                self.lag_estimator.observe(lags)
                # workers landing inside the fold window keep their true
                # products in z; only the effectively-never rows are zeroed
                never = cut & (lags > window)
            else:
                wait, window, cutoff = W, 0, 0.0
                cut = never = masks_all[t]
                lags, observed = None, None
            rate = est.rate
            if self.budget_mode == "telemetry":
                if observed is None:
                    observed = float(cut.mean())
                budget = decode_budget(est.observe(observed), code.l, code.r,
                                       max_rounds=self.max_rounds)
            else:
                budget = int(self.scheme.decode_iters)
            plan = _StepPlan(cut=cut, never=never, lags=lags, wait=int(wait),
                             window=int(window), budget=int(budget),
                             rate=float(rate), cutoff=float(cutoff),
                             observed=observed)
            plan.record()
            if self.master_decode == "replay":
                # Pre-solve the step's peeling schedule NOW: the mask is
                # host data before any device work, so a cold pattern's
                # symbolic solve overlaps the worker matvecs instead of
                # sitting in the decode path; the step's decode then hits
                # the cache unconditionally.
                with _span("master/schedule_solve", lane="master", step=t):
                    self.schedule_cache.get(code, np.asarray(
                        self.topology.to_symbol_erasure(jnp.asarray(cut))))
            ctrl.append(plan)

        use_folds = (delay_model is not None and self.staleness_decay > 0.0
                     and any(c.window > 0 for c in ctrl))
        master = self._get_master_program(with_folds=use_folds,
                                          loss_fn=loss_fn)

        # ---- device-resident carried state ------------------------------
        # θ enters the donated master chain through a FRESH host transfer,
        # so the donation can never alias a buffer the caller (or the
        # replicated broadcast) still holds.
        m = self.master_device
        rep = self._replicated
        theta0_np = np.asarray(theta0)
        theta_m = jax.device_put(theta0_np, m)
        tbar_m = jax.device_put(np.zeros_like(theta0_np), m)
        tstar_m = jax.device_put(
            np.zeros_like(theta0_np) if theta_star is None
            else np.asarray(theta_star), m)
        zero_dg = jax.device_put(np.zeros_like(theta0_np), m)
        fold_budget = np.asarray([self.max_rounds], np.int32)
        theta0_rep = jax.device_put(theta0_np, rep)
        theta_rep: list = []     # broadcast iterates, worker-side inputs
        rec_thetas: list = []

        pend: deque = deque()
        live_folds: list[_FoldEntry] = []
        fold_newly: dict[int, list] = {}
        fold_rounds_at: dict[int, list] = {}
        errors = np.zeros(steps)
        unres = np.zeros(steps, int)
        rounds = np.zeros(steps, int)

        def drain_one():
            # THE queue-pull point: the host blocks on step t's already-
            # dispatched scalars here, so recording/stamping from them adds
            # zero synchronization to the critical path.
            t, nu, r, err, ts_disp = pend.popleft()
            unres[t] = int(nu)
            rounds[t] = int(r)
            errors[t] = float(err)
            _record_step_metrics("pipeline", rounds=int(rounds[t]),
                                 unresolved=int(unres[t]),
                                 budget=ctrl[t].budget)
            tr = _obs_trace.active_tracer()
            if tr is not None:
                # Async-safe stamping: dispatch-time → drain-time span of
                # step t's in-flight window, from host clocks captured when
                # the entry was enqueued (no block_until_ready added).
                tr.complete("pipeline/step", ts_disp,
                            _obs_trace.now_us() - ts_disp, lane="pipeline",
                            step=t, rounds=int(rounds[t]),
                            unresolved=int(unres[t]), budget=ctrl[t].budget)

        for t in range(steps):
            c = ctrl[t]
            # 1. worker launch at the stale iterate θ_{t-depth} — already
            # broadcast, so this dispatch depends on no in-flight decode
            # (depth > 1) and the two programs overlap on the devices.
            ti = t - 1 - tau
            theta_in = theta_rep[ti] if ti >= 0 else theta0_rep
            never_rep = jax.device_put(c.never, rep)
            with _span("worker/launch", lane="worker", step=t):
                z = sync._launch_workers(theta_in, never_rep)

            # 2. folds whose arrivals land THIS step (independent of the
            # current θ, so they overlap the worker launch like the decode)
            fold_dg = zero_dg
            if use_folds:
                reg = _obs_metrics.active()
                still = []
                for entry in live_folds:
                    lag = t - entry.step
                    arriving = entry.cut_mask & (entry.lags == lag)
                    if arriving.any():
                        remaining = entry.cut_mask & (entry.lags > lag)
                        w_tau = np.float32(self.staleness_decay ** lag)
                        with _span("fold/dispatch", lane="fold", step=t,
                                   source_step=entry.step, lag=lag):
                            delta, u2, n_new, fr = self._fold_program(
                                entry.z_m, remaining, entry.u, fold_budget,
                                w_tau, sync._decode_H)
                        entry.u = u2
                        fold_newly.setdefault(entry.step, []).append(n_new)
                        fold_rounds_at.setdefault(t, []).append(fr)
                        fold_dg = (delta if fold_dg is zero_dg
                                   else self._add(fold_dg, delta))
                        if reg is not None:
                            # dispatch-side host facts only — n_new/fr stay
                            # un-fetched device scalars until the end of run
                            reg.counter("pipeline.folds_total").inc()
                            reg.histogram(
                                "pipeline.fold_lag",
                                bins=_obs_metrics.LAG_BINS).observe(lag)
                            reg.histogram(
                                "pipeline.staleness_weight",
                                bins=_obs_metrics.FRACTION_BINS).observe(
                                    float(w_tau))
                    if lag < entry.window and (
                            entry.cut_mask & (entry.lags > lag)).any():
                        still.append(entry)
                live_folds = still

            # 3. fused master launch (decode + update + average + metric);
            # θ̄ is donated through the chain, z/mask arrive zero-copy
            with _span("master/dispatch", lane="master", step=t,
                       budget=c.budget):
                theta_m, tbar_m, nu, r, err, u_mask = master(
                    sync._mshard(z), np.asarray(c.cut), theta_m, tbar_m,
                    fold_dg, np.float32(t),
                    np.asarray([c.budget], np.int32), tstar_m,
                    sync._decode_H)

            # 4. broadcast the new iterate (zero-copy on the master device:
            # the replicated put reuses θ's buffer for the master shard)
            t_rep = jax.device_put(theta_m, rep)
            theta_rep.append(t_rep)
            if record_thetas:
                rec_thetas.append(t_rep)
            if len(theta_rep) > tau + 2:
                theta_rep[t - tau - 1] = None  # release old broadcasts

            # 5. remember this step's survivors if its cut workers can
            # still land inside the fold window
            if use_folds and c.window > 0 and (
                    c.cut & (c.lags > 0)
                    & (c.lags <= c.window)).any():
                live_folds.append(_FoldEntry(
                    step=t, z_m=sync._mshard(z), u=u_mask,
                    cut_mask=c.cut, lags=c.lags, window=c.window))

            pend.append((t, nu, r, err, _obs_trace.now_us()))
            while len(pend) > self.depth:
                drain_one()

        while pend:
            drain_one()

        resolved_late = np.zeros(steps, int)
        for s, counts in fold_newly.items():
            resolved_late[s] = sum(int(n) for n in counts)
        unres = unres - resolved_late
        fold_rounds = np.zeros(steps, int)
        for t, counts in fold_rounds_at.items():
            fold_rounds[t] = sum(int(r) for r in counts)

        reg = _obs_metrics.active()
        if reg is not None:
            # End-of-run totals from the fold scalars that were device
            # values during the loop (fetching them mid-run would have
            # serialized the pipeline), plus the estimator states.
            reg.counter("pipeline.resolved_late_total").inc(
                int(resolved_late.sum()))
            reg.counter("pipeline.fold_rounds_total").inc(
                int(fold_rounds.sum()))
            reg.info("telemetry.straggler_estimator", est.snapshot(),
                     driver="pipeline")
            reg.info("telemetry.arrival_lag_estimator",
                     self.lag_estimator.snapshot(), driver="pipeline")

        thetas = None
        if record_thetas:
            thetas = np.stack([np.asarray(x) for x in rec_thetas])
        return PipelineRunResult(
            theta_m, tbar_m, errors, unres, resolved_late, rounds,
            fold_rounds, np.asarray([c.budget for c in ctrl]),
            np.asarray([c.rate for c in ctrl]),
            np.asarray([c.wait for c in ctrl]),
            np.asarray([c.window for c in ctrl]),
            np.asarray([c.cutoff for c in ctrl]), thetas)
