"""Master side: gather survivors, decode, update — and the end-to-end driver.

:class:`DistributedCodedGD` composes the distributed subsystem into a
master/worker train step over a real device mesh, as TWO device programs —
the same split the paper's Section-5 cluster runs:

  1. **worker program** (one SPMD launch, ``shard_map`` over the
     ``"workers"`` axis, θ broadcast in): each device computes the partial
     products for its row shard of ``C`` and zeroes them if its workers
     straggled (:mod:`repro.distributed.worker`); the program's replicated
     output IS the master's gather of survivor rows (the wait-for-fastest
     semantics live one level up, where the straggler mask is produced —
     :meth:`DistributedCodedGD.run` with a
     :class:`~repro.core.straggler.DelayModel` waits for the fastest
     ``wait_for`` workers per :func:`~repro.core.straggler.DelayModel
     .mask_and_time`, with ``wait_for`` chosen online by telemetry);
  2. **master program** (a single-device launch on the master device):
     peel-decode of whatever arrived through the existing
     :class:`repro.core.engine.CodedComputeEngine` stages — every decode
     backend (dense / sparse / pallas) works unchanged — then the scheme's
     own epilogue and projection, shared verbatim with the single-device
     :class:`repro.core.coded_step.Scheme2`.

The split is what makes the distributed trajectory BIT-IDENTICAL to the
single-device ``Scheme2`` one (tested on the fake 8-device CPU mesh): the
sharded row-block matvec produces the same bits as the full matvec (each
output element is an independent dot product), and the decode runs as a
single-device program on the master instead of being auto-partitioned over
the mesh (an SPMD decode would shard the peeling matmuls' contraction and
change f32 summation order).

Budget policy: ``budget_mode="fixed"`` runs the scheme's fixed-D decode
(the parity configuration); ``budget_mode="telemetry"`` decodes adaptively
under a per-step round budget chosen by the online straggler-rate estimator
(:mod:`repro.distributed.telemetry`).  The budget is a TRACED operand of
the one compiled master program (via the engine's batched-adaptive decode
at B=1), so a drifting straggler climate never recompiles.

``master_decode="sharded"`` replaces step 2's single-device decode with
:mod:`repro.distributed.sharded_decode`: the check-side neighbor table is
partitioned over the ``"workers"`` mesh axis and the per-shard round
results are all-gathered and merged ONCE per round — the peeling update is
per-variable overwrite semantics, not an f32 contraction, so the sharded
decode stays bit-identical to the single-device one (the objection above
applies to AUTO-partitioned dense decodes, not to an explicit check-axis
shard).  Telemetry budgets flow into the sharded program through the same
traced ``(1,)`` operand.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.lax import Precision
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from repro.core.coded_step import Scheme2
from repro.core.decoder import DecodeResult, resolve_backend
from repro.core.engine import blocked_epilogue
from repro.core.straggler import DelayModel
from repro.distributed.sharded_decode import (
    build_sharded_decode,
    shard_check_tables,
)
from repro.distributed.telemetry import (
    StragglerRateEstimator,
    decode_budget,
    pick_wait_for_cached,
)
from repro.distributed.topology import (
    WorkerTopology,
    make_worker_mesh,
    replicated_sharding,
)
from repro.distributed.worker import (
    build_seeded_fused_worker_products,
    build_seeded_worker_products,
    build_worker_products,
    shard_encoded_rows,
    shard_generator_tables,
)
from repro.obs import metrics as _obs_metrics
from repro.obs.trace import span as _span

__all__ = ["DistributedRunResult", "DistributedCodedGD",
           "DistributedCodedAggregator", "build_distributed_gd_step",
           "delay_step_control"]

BUDGET_MODES = ("fixed", "telemetry")
MASTER_DECODES = ("single", "sharded", "replay")
WORKER_ENCODES = ("materialized", "seeded", "seeded-fused")


def master_decode_operand(engine, device):
    """The ``(p, N)`` f32 parity-check matrix the engine's decode reads,
    placed on ``device`` once — or None when the resolved backend reads
    none (sparse tables, seeded tiles and replay schedules are small).

    Master programs take it as an ARGUMENT and decode through
    ``dataclasses.replace(engine, H=H)``: closed over, the matrix would be
    compiled into every program as a constant (512 MiB at N = 16384).
    """
    backend = resolve_backend(engine.backend, engine.code,
                              adaptive=engine.adaptive,
                              vmem_budget_bytes=engine.vmem_budget_bytes)
    if backend not in ("dense", "pallas", "pallas_tiled"):
        return None
    return jax.device_put(jnp.asarray(engine.code.H, jnp.float32), device)


def _record_step_metrics(driver: str, *, rounds: int, unresolved: int,
                         budget: int) -> None:
    """Per-step decode outcome, recorded from ALREADY-FETCHED host ints at
    the point every driver blocks anyway (the ``int(...)`` pulls) — shared
    by the sync driver (``driver="sync"``) and the pipelined one
    (``driver="pipeline"``) so the two emit comparable metric streams."""
    reg = _obs_metrics.active()
    if reg is None:
        return
    reg.counter("distributed.steps_total", driver=driver).inc()
    reg.histogram("distributed.step.rounds", bins=_obs_metrics.ROUND_BINS,
                  driver=driver).observe(rounds)
    reg.histogram("distributed.step.unresolved",
                  bins=_obs_metrics.COUNT_BINS,
                  driver=driver).observe(unresolved)
    reg.histogram("distributed.step.budget", bins=_obs_metrics.ROUND_BINS,
                  driver=driver).observe(budget)
    reg.histogram("distributed.step.budget_headroom",
                  bins=_obs_metrics.ROUND_BINS,
                  driver=driver).observe(max(budget - rounds, 0))


def _record_plan_metrics(driver: str, *, wait_for: int | None = None,
                         rate: float | None = None,
                         observed: float | None = None) -> None:
    """Per-step control-plane decision vs realized straggling: the wait-for
    cut, the EMA estimate ENTERING the step, the observed fraction, and
    their gap (the straggler-rate tracking error)."""
    reg = _obs_metrics.active()
    if reg is None:
        return
    if wait_for is not None:
        reg.histogram("distributed.wait_for", bins=_obs_metrics.COUNT_BINS,
                      driver=driver).observe(wait_for)
    if rate is not None:
        reg.histogram("distributed.straggler.rate_estimate",
                      bins=_obs_metrics.FRACTION_BINS,
                      driver=driver).observe(rate)
    if observed is not None:
        reg.histogram("distributed.straggler.observed",
                      bins=_obs_metrics.FRACTION_BINS,
                      driver=driver).observe(observed)
    if rate is not None and observed is not None:
        reg.histogram("distributed.straggler.tracking_error",
                      bins=_obs_metrics.FRACTION_BINS,
                      driver=driver).observe(abs(rate - observed))


def delay_step_control(delays: np.ndarray, wait_for: int,
                       straggler_factor: float
                       ) -> tuple[np.ndarray, float, float]:
    """Per-step host-side control math for delay-model runs, in ONE numpy
    pass: the straggler mask at the wait-for cutoff, the cutoff itself
    (the step's simulated wall-clock), and the telemetry observation
    (fraction of workers slower than ``straggler_factor`` × the waited-for
    median — NOT the mask, which is the cut the estimator itself chose).

    Shared by the synchronous driver and the pipelined one
    (:mod:`repro.distributed.pipeline`) so the two runtimes realize
    IDENTICAL masks from identical delays — the depth-1 bit-parity gate
    rests on it.  Returns ``(mask (W,) bool, cutoff, observed_fraction)``.
    """
    delays = np.asarray(delays)
    order = np.argsort(delays, kind="stable")
    cutoff = float(delays[order[wait_for - 1]])
    mask = delays > cutoff  # stragglers: slower than the wait-for cutoff
    med = float(np.median(delays[order[:wait_for]]))
    observed = float((delays > straggler_factor * med).mean())
    return mask, cutoff, observed


class DistributedRunResult(NamedTuple):
    theta: jax.Array        # final iterate
    theta_bar: jax.Array    # running average (Theorem 1 is stated for it)
    errors: np.ndarray      # (T,) ||θ_t - θ*|| (or loss / norm)
    unresolved: np.ndarray  # (T,) |U_t| per step
    rounds: np.ndarray      # (T,) decode rounds actually spent per step
    budgets: np.ndarray     # (T,) round budget granted per step
    rates: np.ndarray       # (T,) telemetry estimate q̂ entering each step
    wait_for: np.ndarray    # (T,) workers waited for (delay-model runs; else W)
    step_times: np.ndarray  # (T,) simulated wall-clock (delay-model runs; else 0)


@dataclasses.dataclass
class DistributedCodedGD:
    """Moment-encoded GD over a worker mesh, driven from a master loop.

    ``scheme`` supplies the code, the encoded operator ``C``, the moment
    vector ``b``, the learning rate, the decode backend, and the gradient
    epilogue — everything the single-device path uses, reused verbatim.
    ``topology`` fixes the row→worker assignment (``W`` logical workers);
    ``mesh`` places the workers onto devices (``n_devices | W``).
    """

    scheme: Scheme2
    topology: WorkerTopology
    mesh: Mesh | None = None
    budget_mode: str = "fixed"
    # "single": decode as one single-device program on the master (the
    # default — any engine backend).  "sharded": the decode itself runs
    # over the workers mesh with check tiles partitioned across devices
    # (repro.distributed.sharded_decode) — for N past one device; stays
    # bit-identical to the single-device sparse decode.  "replay": the
    # pattern-compiled decode — the step's concrete mask (known on the host
    # at dispatch) looks its peeling schedule up in a cross-step
    # ScheduleCache (recurring straggler patterns pay the symbolic solve
    # once) and the decode is the straight-line numeric replay; stays
    # bit-identical to the single-device sparse decode.
    master_decode: str = "single"
    # "materialized": workers hold their rows of the encoded C (the default
    # — scheme.C is the (N, k) encoded operator, row-sharded over the mesh).
    # "seeded": workers hold ONLY their slice of the seeded generator gather
    # tables and fuse encode into the matvec (z = gather(M θ) per row);
    # requires a Scheme2.build_seeded scheme (scheme.C is then the raw M).
    # Products — hence trajectories — are bit-identical across the two.
    # "seeded-fused": like "seeded" but the gather runs inside the fused
    # Pallas encode kernel with indices regenerated in-register from the
    # seed — workers hold NO tables at all.  Bit-identical to a reference
    # Scheme2 built with encode_fused=True (kernel on both sides).
    worker_encode: str = "materialized"
    estimator: StragglerRateEstimator | None = None
    max_rounds: int | None = None     # telemetry worst-case budget ceiling
    # Delay-model runs: a worker counts as STRAGGLING when its latency
    # exceeds straggler_factor × the median of the waited-for arrivals.
    # This is what telemetry observes under a DelayModel — observing the
    # erasure mask itself would be circular there (the mask is exactly the
    # wait-for cut the estimator chose, so q̂ would converge to its own
    # decision instead of to anything about the workers).
    straggler_factor: float = 2.0
    # master_decode="replay" only: the cross-step LRU of compiled peeling
    # schedules.  None = the driver builds its own; pass one to share it
    # (e.g. the pipelined driver hands its cache to the wrapped sync
    # driver so warm patterns carry across).
    schedule_cache: object | None = None

    def __post_init__(self) -> None:
        if self.budget_mode not in BUDGET_MODES:
            raise ValueError(f"unknown budget_mode {self.budget_mode!r}; "
                             f"want one of {BUDGET_MODES}")
        if self.master_decode not in MASTER_DECODES:
            raise ValueError(f"unknown master_decode {self.master_decode!r}; "
                             f"want one of {MASTER_DECODES}")
        if self.worker_encode not in WORKER_ENCODES:
            raise ValueError(f"unknown worker_encode {self.worker_encode!r}; "
                             f"want one of {WORKER_ENCODES}")
        if (self.worker_encode in ("seeded", "seeded-fused")
                and not self.scheme.seeded_encode):
            raise ValueError(
                f"worker_encode={self.worker_encode!r} needs a "
                "Scheme2.build_seeded scheme (seeded_encode=True, C holding "
                "the raw moment matrix M); this scheme stores a "
                "materialized encoded operator")
        if self.topology.N != self.scheme.w:
            raise ValueError(
                f"topology covers N={self.topology.N} rows but the scheme's "
                f"code has N={self.scheme.w}")
        if self.mesh is None:
            self.mesh = make_worker_mesh()
        self.topology.validate_mesh(self.mesh)
        if self.estimator is None:
            self.estimator = StragglerRateEstimator()
        if self.max_rounds is None:
            self.max_rounds = int(self.scheme.decode_iters)
        self._replicated = replicated_sharding(self.mesh)
        if self.worker_encode in ("seeded", "seeded-fused"):
            # Workers never hold encoding-matrix rows: the raw moment matrix
            # M (scheme.C under seeded_encode) is replicated problem data.
            # Plain "seeded" shards the generator gather tables; the fused
            # mode regenerates indices in-kernel and needs no tables at all.
            if self.worker_encode == "seeded":
                self._tables_sharded = shard_generator_tables(
                    self.scheme.code, self.mesh, self.topology)
            self._M_replicated = jax.device_put(
                jnp.asarray(self.scheme.C), self._replicated)
        else:
            self._C_sharded = shard_encoded_rows(
                jnp.asarray(self.scheme.C), self.mesh, self.topology)
        self.master_device = self.mesh.devices.flat[0]
        self._decode_H = (master_decode_operand(self.scheme.engine,
                                                self.master_device)
                          if self.master_decode == "single" else None)
        if self.master_decode == "sharded":
            # Check tiles partitioned over the workers axis, once at build.
            self._sharded_tables = shard_check_tables(self.scheme.code,
                                                      self.mesh)
        if self.master_decode == "replay" and self.schedule_cache is None:
            from repro.core.schedule_cache import ScheduleCache
            self.schedule_cache = ScheduleCache()
        # Which addressable shard of a replicated array lives on the master
        # device: the worker program's replicated output hands the master
        # program its operand ZERO-COPY via that shard's buffer, instead of
        # a fresh device_put per step.
        probe = jax.device_put(jnp.zeros((1,)), self._replicated)
        self._mshard_idx = next(
            i for i, s in enumerate(probe.addressable_shards)
            if s.device == self.master_device)
        self._worker_program, self._master_program = self._build_programs()

    def _mshard(self, x: jax.Array) -> jax.Array:
        """The master device's shard of a replicated array — a zero-copy
        single-device view, usable as a master-program operand."""
        return x.addressable_shards[self._mshard_idx].data

    def _launch_workers(self, theta_rep: jax.Array,
                        mask_rep: jax.Array) -> jax.Array:
        """One SPMD worker launch with the operands the built program wants:
        the per-mode operator placement (sharded C rows / sharded gather
        tables + replicated M / replicated M alone) plus the replicated
        broadcast.  Shared by :meth:`step` and the pipelined driver so the
        worker-encode dispatch lives exactly once."""
        if self.worker_encode == "seeded":
            idx_sh, coeff_sh = self._tables_sharded
            return self._worker_program(idx_sh, coeff_sh, self._M_replicated,
                                        theta_rep, mask_rep)
        if self.worker_encode == "seeded-fused":
            return self._worker_program(self._M_replicated, theta_rep,
                                        mask_rep)
        return self._worker_program(self._C_sharded, theta_rep, mask_rep)

    # ------------------------------------------------------------ step build

    @property
    def n_workers(self) -> int:
        return self.topology.n_workers

    def _build_programs(self):
        scheme, topo = self.scheme, self.topology
        eng = scheme.engine

        # Worker program: ONE SPMD launch over the workers axis.  θ and the
        # per-worker mask come in replicated (the master's broadcast), each
        # device computes/erases only its own rows, and the replicated
        # output is the master's gather of survivor rows.
        if self.worker_encode == "seeded":
            seeded_products = build_seeded_worker_products(self.mesh)

            def worker_program(idx_sh, coeff_sh, M, theta, worker_mask):
                erased = topo.to_symbol_erasure(worker_mask)  # partition lift
                return seeded_products(idx_sh, coeff_sh, M, theta, erased)
        elif self.worker_encode == "seeded-fused":
            fused_products = build_seeded_fused_worker_products(
                scheme.code, self.mesh)

            def worker_program(M, theta, worker_mask):
                erased = topo.to_symbol_erasure(worker_mask)  # partition lift
                return fused_products(M, theta, erased)
        else:
            worker_products = build_worker_products(self.mesh)

            def worker_program(C_sh, theta, worker_mask):
                erased = topo.to_symbol_erasure(worker_mask)  # partition lift
                return worker_products(C_sh, theta, erased)

        worker_jit = jax.jit(worker_program, out_shardings=self._replicated)

        if self.master_decode == "sharded":
            # Sharded master program: the decode runs over the SAME mesh,
            # check tiles partitioned across devices, values replicated; the
            # scheme's epilogue/update stays replicated elementwise math.
            # Both budget modes flow through the traced (1,) budget operand
            # (the fixed program bakes its round count in statically and
            # ignores it, mirroring the single-device fixed program).
            eng_iters = int(eng.decode_iters)
            decode_fn = build_sharded_decode(
                self.mesh, iters=eng_iters,
                adaptive=self.budget_mode == "telemetry")
            fixed_mode = self.budget_mode == "fixed"

            def master_program(idx_sh, coeff_sh, z, worker_mask, theta,
                               budget):
                erased = topo.to_symbol_erasure(worker_mask)
                with jax.named_scope("decode"):
                    z = eng.erase(z, erased)  # idempotent, mirrors recover()
                    vals, e2, rounds = decode_fn(idx_sh, coeff_sh,
                                                 z[:, None], erased, budget)
                    dec = DecodeResult(vals[:, 0], e2, rounds)
                    c_hat, unresolved = eng.systematic(dec)
                with jax.named_scope("update"):
                    g, n_unres = scheme.finish_gradient(c_hat, unresolved)
                    theta2 = scheme.projection(theta - scheme.lr * g)
                return theta2, n_unres, (jnp.int32(eng_iters) if fixed_mode
                                         else rounds)

            return worker_jit, jax.jit(master_program)

        if self.master_decode == "replay":
            # Replay master program: the decode dispatch stays EAGER — the
            # step's mask is concrete on the host at dispatch, so the
            # engine looks the pattern's compiled schedule up in the
            # cross-step cache (hit → no symbolic solve) and the numeric
            # replay jits internally keyed on the schedule's segment
            # shapes.  Only the value-level epilogue/update is jitted
            # here.  Replay reproduces the sparse flooding arithmetic
            # bit-for-bit, so the sync-parity gates hold unchanged.
            r_eng = dataclasses.replace(eng, backend="replay",
                                        schedule_cache=self.schedule_cache)
            fixed_mode = self.budget_mode == "fixed"

            @jax.jit
            def replay_epilogue(values, erased, theta):
                with jax.named_scope("decode"):
                    c_hat, unresolved = eng.systematic(
                        DecodeResult(values, erased, jnp.int32(0)))
                with jax.named_scope("update"):
                    g, n_unres = scheme.finish_gradient(c_hat, unresolved)
                    theta2 = scheme.projection(theta - scheme.lr * g)
                return theta2, n_unres

            def master_program(z, worker_mask, theta, budget, H):
                del H             # replay reads its schedule, never H
                erased = topo.to_symbol_erasure(worker_mask)
                z = r_eng.erase(z, erased)    # idempotent, mirrors recover()
                if fixed_mode:
                    dec = r_eng.decode(z, erased)
                    values, er2, rounds = (dec.values, dec.erased,
                                           dec.rounds_used)
                else:
                    dec = r_eng.decode_batch(z[None], erased[None],
                                             adaptive=True, budgets=budget)
                    values, er2, rounds = (dec.values[0], dec.erased[0],
                                           dec.rounds_used[0])
                theta2, n_unres = replay_epilogue(values, er2, theta)
                return theta2, n_unres, rounds

            return worker_jit, master_program

        # Master program: a SINGLE-DEVICE launch (inputs committed to the
        # master device pin it there) — decode of the gathered survivors
        # plus the scheme's own epilogue/update, shared verbatim with the
        # single-device Scheme2 so the two paths cannot diverge.  erase()
        # on the already-zeroed survivors is idempotent, so the decode sees
        # exactly what Scheme2.gradient feeds it.
        if self.budget_mode == "fixed":
            def master_program(z, worker_mask, theta, budget, H):
                del budget  # fixed-D decode; kept for a stable signature
                erased = topo.to_symbol_erasure(worker_mask)
                with jax.named_scope("decode"):
                    c_hat, unresolved = dataclasses.replace(
                        eng, H=H).recover(z, erased)
                with jax.named_scope("update"):
                    g, n_unres = scheme.finish_gradient(c_hat, unresolved)
                    theta2 = scheme.projection(theta - scheme.lr * g)
                return theta2, n_unres, jnp.int32(eng.decode_iters)
        else:
            # Telemetry mode rides the engine's batched-adaptive decode at
            # B=1: the round budget is a TRACED (1,) operand (changing
            # budgets never recompile) and rounds_used surfaces per step.
            def master_program(z, worker_mask, theta, budget, H):
                erased = topo.to_symbol_erasure(worker_mask)
                with jax.named_scope("decode"):
                    dec = dataclasses.replace(eng, H=H).decode_batch(
                        z[None], erased[None], adaptive=True, budgets=budget)
                    c_hat, unresolved = eng.systematic(dec)
                with jax.named_scope("update"):
                    g, n_unres = scheme.finish_gradient(c_hat[0],
                                                        unresolved[0])
                    theta2 = scheme.projection(theta - scheme.lr * g)
                return theta2, n_unres, dec.rounds_used[0]

        return worker_jit, jax.jit(master_program)

    # --------------------------------------------------------------- driving

    def step(self, theta: jax.Array, worker_mask: jax.Array, *,
             observed_fraction: float | None = None
             ) -> tuple[jax.Array, int, int, int]:
        """One master step from a realized (W,) worker straggler mask.

        Telemetry observes BEFORE the decode budget is chosen — the master
        knows exactly which workers reported when it starts decoding.  The
        default observation is the mask's straggler fraction (right for
        straggler-model runs, where the mask is exogenous);
        ``observed_fraction`` overrides it for callers whose mask is a
        policy DECISION rather than a measurement (delay-model runs pass a
        latency-derived fraction — see :meth:`run`).  Returns
        ``(θ', n_unresolved, rounds_spent, budget)``.

        Host spans (free when tracing is off): ``step/inputs`` (mask,
        budget and broadcasts), ``worker/launch`` (the worker program's
        enqueue) and ``master/decode``, which holds ``master/dispatch``
        (the master program's operands and enqueue) and ``master/fetch``
        (the blocking pull of the two scalars).
        """
        with _span("step/inputs", lane="master"):
            worker_mask = jnp.asarray(worker_mask, bool)
            if worker_mask.shape != (self.n_workers,):
                raise ValueError(f"worker_mask must be ({self.n_workers},); "
                                 f"got {worker_mask.shape}")
            if self.budget_mode == "telemetry":
                if observed_fraction is None:
                    observed_fraction = float(
                        self.topology.observed_fraction(worker_mask))
                rate_in = self.estimator.rate  # estimate ENTERING the step
                rate = self.estimator.observe(observed_fraction)
                code = self.scheme.code
                budget = decode_budget(rate, code.l, code.r,
                                       max_rounds=self.max_rounds)
                _record_plan_metrics("sync", rate=rate_in,
                                     observed=observed_fraction)
            else:
                budget = int(self.scheme.decode_iters)
            # broadcast θ + mask to the workers, one SPMD partial-product
            # launch.  device_put is a no-op when the operand already
            # carries the replicated sharding (θ handed back by a previous
            # step), so a driver loop pays ONE broadcast per array per
            # step, not the old replicated-put + master-put pair.
            theta_rep = jax.device_put(theta, self._replicated)
            mask_rep = jax.device_put(worker_mask, self._replicated)
            budget_arr = np.asarray([budget], np.int32)
        with _span("worker/launch", lane="worker"):
            z = self._launch_workers(theta_rep, mask_rep)
        with _span("master/decode", lane="master", budget=budget):
            with _span("master/dispatch", lane="master"):
                if self.master_decode == "sharded":
                    # decode over the mesh: check tiles stay sharded;
                    # z/θ/mask are already replicated (z is the worker
                    # program's output sharding)
                    idx_sh, coeff_sh = self._sharded_tables
                    theta2, n_unres, rounds = self._master_program(
                        idx_sh, coeff_sh, z, mask_rep, theta_rep,
                        jax.device_put(jnp.asarray(budget_arr),
                                       self._replicated))
                else:
                    # master-local decode + update: operands are the master
                    # device's OWN shards of the replicated worker output /
                    # broadcast (zero-copy views), plus the budget scalar
                    # which jit places alongside them.
                    theta2, n_unres, rounds = self._master_program(
                        self._mshard(z), self._mshard(mask_rep),
                        self._mshard(theta_rep), budget_arr, self._decode_H)
            with _span("master/fetch", lane="master"):
                n_unres, rounds = int(n_unres), int(rounds)
        _record_step_metrics("sync", rounds=rounds, unresolved=n_unres,
                             budget=budget)
        return theta2, n_unres, rounds, budget

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
    ) -> DistributedRunResult:
        """Drive ``steps`` master steps.

        ``straggler_model`` samples per-WORKER masks (width ``W``) with the
        same key schedule as :func:`repro.core.coded_step.run_pgd` (one
        ``jax.random.split`` of ``key``), so a single-device reference run
        under the lifted mask sees identical erasure realizations.  With a
        ``delay_model``, masks instead come from per-worker latencies and a
        telemetry-chosen wait-for-fastest threshold (the paper's Section-5
        timing model); ``step_times`` then records the simulated wall-clock
        of each step (the order statistic at the cutoff).
        """
        if key is None:
            key = jax.random.PRNGKey(0)
        keys = jax.random.split(key, steps)
        W = self.n_workers
        code = self.scheme.code

        def metric(theta):
            if theta_star is not None:
                return jnp.linalg.norm(theta - theta_star)
            if loss_fn is not None:
                return loss_fn(theta)
            return jnp.linalg.norm(theta)

        theta = jnp.asarray(theta0)
        tbar = jnp.zeros_like(theta)
        errors, unresolved, rounds, budgets, rates, waits, times = \
            [], [], [], [], [], [], []
        for t in range(steps):
            observed = None
            if delay_model is not None:
                wait = pick_wait_for_cached(self.estimator.rate, W,
                                            code.l, code.r)
                delays = np.asarray(delay_model.sample_delays(keys[t], W))
                # One host-side numpy pass: mask at the cutoff, simulated
                # step time, and the telemetry observation (tail latency
                # relative to the waited-for median, NOT the mask — the
                # mask is the cut the estimator itself chose; observing it
                # would close a feedback loop where q̂ converges to its own
                # decision and homogeneous fast fleets keep getting cut
                # forever).
                worker_mask, cutoff, observed = delay_step_control(
                    delays, wait, self.straggler_factor)
                times.append(cutoff)
            else:
                wait = W
                worker_mask = straggler_model.sample(keys[t], W)
                times.append(0.0)
            rates.append(self.estimator.rate)
            _record_plan_metrics("sync", wait_for=int(wait))
            theta, n_unres, spent, budget = self.step(
                theta, worker_mask, observed_fraction=observed)
            tbar = (tbar * t + theta) / (t + 1.0)
            errors.append(float(metric(theta)))
            unresolved.append(n_unres)
            rounds.append(spent)
            budgets.append(budget)
            waits.append(int(wait))
        reg = _obs_metrics.active()
        if reg is not None:
            reg.info("telemetry.straggler_estimator",
                     self.estimator.snapshot(), driver="sync")
        return DistributedRunResult(
            theta, tbar, np.asarray(errors), np.asarray(unresolved),
            np.asarray(rounds), np.asarray(budgets), np.asarray(rates),
            np.asarray(waits), np.asarray(times))


# ------------------------------------------ distributed coded aggregation


@dataclasses.dataclass
class DistributedCodedAggregator:
    """The beyond-paper additive-loss path served by the worker runtime.

    :class:`repro.core.grad_agg.CodedAggregator` run as the SAME two device
    programs as :class:`DistributedCodedGD`: the generator rows are sharded
    over the ``"workers"`` mesh axis and each device computes its rows of
    ``G @ partials`` — a 2-D-payload :func:`repro.distributed.worker
    .build_worker_products` launch (each systematic symbol is a flattened
    ``(dim,)`` partial gradient) — then the master peels the survivor
    symbols and sums the recovered shards.  The decode runs as a
    single-device program on the master, so the erasure trajectory (and
    unresolved count) matches the single-device
    :meth:`CodedAggregator.aggregate` under the lifted mask exactly; the
    sums agree within the decoder's value contract, since a row-block GEMM
    may block its f32 sums differently from the full ``G @ partials``
    (asserted by ``repro.distributed.selfcheck --grad-agg`` on the fake
    8-device mesh).
    """

    agg: "CodedAggregator"
    topology: WorkerTopology
    mesh: Mesh | None = None

    def __post_init__(self) -> None:
        from repro.core.grad_agg import CodedAggregator
        if not isinstance(self.agg, CodedAggregator):
            raise TypeError(f"agg must be a CodedAggregator; "
                            f"got {type(self.agg).__name__}")
        if self.topology.N != self.agg.n_workers:
            raise ValueError(
                f"topology covers N={self.topology.N} rows but the "
                f"aggregator's code has N={self.agg.n_workers}")
        if self.mesh is None:
            self.mesh = make_worker_mesh()
        self.topology.validate_mesh(self.mesh)
        self._G_sharded = shard_encoded_rows(
            jnp.asarray(self.agg.code.G, jnp.float32), self.mesh,
            self.topology)
        self._replicated = replicated_sharding(self.mesh)
        self.master_device = self.mesh.devices.flat[0]

        topo, agg = self.topology, self.agg
        worker_products = build_worker_products(self.mesh)
        eng = agg.engine

        def worker_program(G_sh, partials, worker_mask):
            erased = topo.to_symbol_erasure(worker_mask)
            return worker_products(G_sh, partials, erased)

        def master_program(z, worker_mask, H):
            erased = topo.to_symbol_erasure(worker_mask)
            with jax.named_scope("decode"):
                recovered, unresolved = dataclasses.replace(
                    eng, H=H).recover(z, erased)
            with jax.named_scope("sum"):
                total = recovered.sum(axis=0) * agg.debias_scale
                return total, unresolved.sum()

        self._worker_program = jax.jit(worker_program,
                                       out_shardings=self._replicated)
        self._master_program = jax.jit(master_program)
        self._decode_H = master_decode_operand(eng, self.master_device)

    @property
    def n_workers(self) -> int:
        return self.topology.n_workers

    def aggregate(self, partials: jax.Array, worker_mask: jax.Array
                  ) -> tuple[jax.Array, int]:
        """Coded sum of ``partials (K, dim)`` under a ``(W,)`` worker mask.

        One SPMD worker launch (sharded generator rows, 2-D payload), one
        master decode launch.  Returns ``(Σ_i ĝ_i (dim,), n_unresolved)``.
        Host spans as :meth:`DistributedCodedGD.step`'s.
        """
        with _span("step/inputs", lane="master"):
            partials = jnp.asarray(partials)
            worker_mask = jnp.asarray(worker_mask, bool)
            if worker_mask.shape != (self.n_workers,):
                raise ValueError(f"worker_mask must be ({self.n_workers},); "
                                 f"got {worker_mask.shape}")
            partials_rep = jax.device_put(partials, self._replicated)
            mask_rep = jax.device_put(worker_mask, self._replicated)
        with _span("worker/launch", lane="worker"):
            z = self._worker_program(self._G_sharded, partials_rep, mask_rep)
        m = self.master_device
        with _span("master/decode", lane="master"):
            with _span("master/dispatch", lane="master"):
                total, n_unres = self._master_program(
                    jax.device_put(z, m), jax.device_put(worker_mask, m),
                    self._decode_H)
            with _span("master/fetch", lane="master"):
                n_unres = int(n_unres)
        return total, n_unres


# ------------------------------------------------- production-scale AOT step


def build_distributed_gd_step(k: int, K: int, decode_iters: int, dtype,
                              mesh: Mesh, *, decode: str = "sparse",
                              r: int = 6):
    """Sharded-worker Scheme2Blocked step at production scale, for AOT
    lower/compile analysis (:mod:`repro.launch.paper_dryrun`'s
    ``--distributed`` variant).

    Unlike :func:`repro.launch.steps.build_coded_gd_step` (which shards the
    encoded operator as an undifferentiated tensor), this step places the
    pipeline the way the real system runs it: the mesh carries an explicit
    ``("workers", "data")`` layout, the worker compute is a ``shard_map``
    over the ``"workers"`` axis (each chip holds its workers' rows of every
    block and contributes partial sums over its ``"data"`` slice of θ, with
    one ``psum`` over "data"), the straggler mask is PER-WORKER ``(W,)``
    (W = the workers-axis size) lifted to symbols inside the step, and the
    master decode runs on the gathered survivors through the shared
    :mod:`repro.core.decoder` fixed-D loops + engine epilogue.

    Returns ``(jitted_step, arg_specs)`` ready for AOT lower/compile.
    """
    from jax.sharding import NamedSharding
    from repro.core.decoder import peel_fixed_dense, peel_fixed_sparse

    N, p, nb = 2 * K, K, k // K
    W = mesh.shape["workers"]
    topo = WorkerTopology(W, N)
    sh = lambda *spec: NamedSharding(mesh, P(*spec))

    def worker_fn(C_shard, theta_shard, erased_shard):
        # C_shard (nb, N/W, k/data); theta_shard (k/data,) — partial sums
        # over the feature axis, one psum over "data" completes the dot.
        z = jnp.einsum("bnk,k->nb", C_shard,
                       theta_shard.astype(C_shard.dtype),
                       precision=Precision.HIGHEST)
        z = jax.lax.psum(z.astype(jnp.float32), "data")
        return jnp.where(erased_shard[:, None], 0.0, z)

    worker_products = shard_map(
        worker_fn, mesh=mesh,
        in_specs=(P(None, "workers", "data"), P("data"), P("workers")),
        out_specs=P("workers", None))

    def epilogue(vals, erased_sym, theta, b, lr):
        g, _ = blocked_epilogue(vals, erased_sym, b, K=K, nb=nb)
        return theta - lr * g

    def to_master(*xs):
        # The master decode consumes the survivors and its tables in the
        # MASTER layout (replicated: every chip runs the same unpartitioned
        # decode), resharded explicitly — under an Explicit-axes mesh the
        # row-sharded worker output cannot feed the decode's scatters.
        return tuple(jax.sharding.reshard(x, sh()) for x in xs)

    common = (
        jax.ShapeDtypeStruct((k,), jnp.float32),   # theta
        jax.ShapeDtypeStruct((k,), jnp.float32),   # b
        jax.ShapeDtypeStruct((W,), jnp.bool_),     # PER-WORKER mask
        jax.ShapeDtypeStruct((), jnp.float32),     # lr
    )
    common_sh = (sh(), sh(), sh(), sh())
    c_spec = jax.ShapeDtypeStruct((nb, N, k), dtype)
    c_sh = sh(None, "workers", "data")

    if decode == "dense":
        def step_dense(C_blocks, H, theta, b, worker_mask, lr):
            erased = topo.to_symbol_erasure(worker_mask)
            z = worker_products(C_blocks, theta, erased)
            H, z, erased = to_master(H, z, erased)
            vals, er = peel_fixed_dense(H, H != 0.0, z, erased, decode_iters)
            return epilogue(vals, er, theta, b, lr)

        args = (c_spec, jax.ShapeDtypeStruct((p, N), jnp.float32), *common)
        in_sh = (c_sh, sh("workers", None), *common_sh)
        return jax.jit(step_dense, in_shardings=in_sh,
                       out_shardings=sh()), args

    if decode != "sparse":
        raise ValueError(f"unknown distributed decode variant {decode!r}; "
                         "want dense|sparse")

    def step_sparse(C_blocks, H_idx, H_val, theta, b, worker_mask, lr):
        erased = topo.to_symbol_erasure(worker_mask)
        z = worker_products(C_blocks, theta, erased)
        H_idx, H_val, z, erased = to_master(H_idx, H_val, z, erased)
        vals, er = peel_fixed_sparse(H_idx, H_val, z, erased, decode_iters)
        return epilogue(vals, er, theta, b, lr)

    args = (c_spec, jax.ShapeDtypeStruct((p, r), jnp.int32),
            jax.ShapeDtypeStruct((p, r), jnp.float32), *common)
    in_sh = (c_sh, sh("workers", None), sh("workers", None), *common_sh)
    return jax.jit(step_sparse, in_shardings=in_sh, out_shardings=sh()), args
