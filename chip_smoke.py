#!/usr/bin/env python3
"""Chip smoke test: the paper's moment-encoded GD through the mesh drivers
on a TPU, checked against a plain float32 reference.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --chips 4      # four chips: the sharded-mesh path

Builds the paper's least-squares problem from ``--seed`` at ``k = K``
(default 8192: ``make_linear_problem(m=4k)``, the (3,6)-regular rate-1/2
``make_regular_ldpc`` code, so N = 2k, and W = 32 logical workers), then

* phase A drives :class:`repro.distributed.master.DistributedCodedGD` for
  ``--steps`` steps with the scheme's ``sparse`` decode, ``auto`` (on TPU
  this resolves to a Pallas kernel; the script asserts the master program
  lowers to a ``tpu_custom_call``) and ``master_decode="replay"``, first
  without stragglers (q = 0), then with per-worker Bernoulli stragglers
  (``WorkerStragglers``, q = 0.1);
* phase B drives :class:`repro.distributed.pipeline.AsyncDistributedCodedGD`
  at depth 2 with a two-step fold window (sparse decode): once without
  stragglers against the plain delayed-gradient reference, once under a
  shifted-exponential delay model, where late workers fold back in;
* ``--chips 4`` runs only ``DistributedCodedGD`` on a 4-device
  ``"workers"`` mesh (8 logical workers per chip), ``master_decode``
  ``single`` and ``sharded``, against the one-device reference.

Checks (any failure raises; the process exits non-zero):

* every step: the driver's unresolved count equals the count
  ``compile_peel_schedule`` solves on the host for that mask under the
  step's round budget (exact); coordinates left unresolved keep their
  iterate exactly (a zero-filled gradient, Lemma 1); resolved ones match
  the exact gradient ``M θ − b`` at the driver's own iterate within the
  peel-chain bound of :func:`repro.core.decoder.peel_error_bound`, fed the
  f32 rounding error of the workers' products.  Rounding model: an n-term
  f32 dot is off by at most ``√n·u·Σ|terms|`` with ``u`` =
  ``F32_OP_ERROR`` (4 ulps) — the probabilistic bound of Higham & Mary
  (SIAM J. Sci. Comput., 2019); the worst case ``n·u`` would make the
  q = 0 tolerance below about 30% at k = 8192, too loose to detect a
  matvec run at bf16;
* without stragglers: ``‖θ_T − θ_T^ref‖₂ <= tol_T``, with ``θ^ref`` the
  plain f32 reference ``θ ← θ − lr·(Mθ − b)`` (``precision=HIGHEST``;
  delayed by the pipeline depth for phase B) and ``tol_T`` the sum over
  steps of both sides' per-step f32 error bounds (the update contracts
  errors: ``‖I − lr·M‖₂ <= 1`` for ``lr = 1/λ_max``) — it is printed as
  the relative ``rtol``;
* the loss ``½θᵀMθ − bᵀθ`` decreases at every synchronous step and over
  each pipelined run.

Each phase prints one JSON line (sizes, device kind, compile seconds from
JAX's own compile events, peak device bytes, check results); the last line
is ``{"ok": true, "device": {...}}``.  Without a TPU the script exits 2
before doing any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import NamedTuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import BernoulliStragglers, DelayModel, Scheme2  # noqa: E402
from repro.core.decoder import (  # noqa: E402
    F32_OP_ERROR,
    peel_error_bound,
    resolve_backend,
)
from repro.core.encoding import encode_moment, second_moment  # noqa: E402
from repro.core.ldpc import make_regular_ldpc  # noqa: E402
from repro.data import make_linear_problem  # noqa: E402
from repro.distributed.master import DistributedCodedGD  # noqa: E402
from repro.distributed.pipeline import AsyncDistributedCodedGD  # noqa: E402
from repro.distributed.topology import (  # noqa: E402
    WorkerTopology,
    make_worker_mesh,
)
from repro.distributed.worker import WorkerStragglers  # noqa: E402

DECODE_ITERS = 10
STRAGGLER_Q = 0.1
HIGHEST = jax.lax.Precision.HIGHEST
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    monitoring events (one listener per clock, for the process's life)."""

    def __init__(self) -> None:
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.total += duration


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes(devices=None) -> list:
    """``peak_bytes_in_use`` per device (None where the backend keeps no
    statistics, as the CPU backend does)."""
    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats()
        out.append(None if stats is None
                   else int(stats.get("peak_bytes_in_use", 0)))
    return out


# --------------------------------------------------------------- problem


class Problem(NamedTuple):
    code: object
    topo: WorkerTopology
    base: Scheme2          # sparse decode; other configs replace() it
    M: jax.Array           # (k, k) f32 on the device
    b: jax.Array           # (k,) f32
    lr: float
    # host float64 copies the checks use
    M64: np.ndarray
    absM: np.ndarray
    b64: np.ndarray
    G64: np.ndarray
    absG: np.ndarray
    absC: np.ndarray       # |C| of the device's f32 encoded operator
    G_nnz: np.ndarray      # nonzeros per generator row (1: systematic)
    setup: dict


def build_problem(k: int, workers: int, seed: int) -> Problem:
    """The paper's least squares at k = K, its code, and Scheme 2."""
    t0 = time.perf_counter()
    prob = make_linear_problem(m=4 * k, k=k, seed=seed)
    t1 = time.perf_counter()
    code = make_regular_ldpc(k, l=3, r=6, seed=seed)
    t2 = time.perf_counter()
    mom = second_moment(prob.X, prob.y)
    lr = float(prob.lr)
    del prob                        # the moments are all the scheme keeps
    C = encode_moment(code, mom.M)
    base = Scheme2(code=code, C=C, b=mom.b, lr=lr,
                   decode_iters=DECODE_ITERS, decode_backend="sparse")
    M64 = np.asarray(mom.M, np.float64)
    G64 = np.asarray(code.G, np.float64)
    absC = np.abs(np.asarray(C, np.float64))
    t3 = time.perf_counter()
    return Problem(
        code=code, topo=WorkerTopology(workers, code.N), base=base,
        M=mom.M, b=mom.b, lr=lr, M64=M64, absM=np.abs(M64),
        b64=np.asarray(mom.b, np.float64), G64=G64, absG=np.abs(G64),
        absC=absC, G_nnz=np.count_nonzero(G64, axis=1),
        setup={"problem_s": round(t1 - t0, 3), "code_s": round(t2 - t1, 3),
               "encode_s": round(t3 - t2, 3)})


def loss(pb: Problem, theta) -> float:
    """½θᵀMθ − bᵀθ: the least-squares loss up to its constant."""
    th = np.asarray(theta, np.float64)
    return float(0.5 * th @ (pb.M64 @ th) - pb.b64 @ th)


def gradient_error(pb: Problem, theta, erased) -> tuple:
    """Per step: the exact gradient at ``theta``, the mask of systematic
    coordinates the decode resolves within ``DECODE_ITERS`` rounds, and
    the f32 error bound of a resolved coordinate's coded gradient."""
    u, k = F32_OP_ERROR, pb.code.K
    th = np.asarray(theta, np.float64)
    Mth = pb.M64 @ th
    z = pb.G64 @ Mth                              # exact codeword G·M·θ
    abs_th = np.abs(th)
    # f32 worker products: the k-term dot over C's row, plus C's own f32
    # encode error (a dot over the row's generator nonzeros, f32 G's own
    # rounding included) carried through θ
    z_err = (np.sqrt(k) * u * (pb.absC @ abs_th)
             + np.sqrt(pb.G_nnz + 1) * u * (pb.absG @ (pb.absM @ abs_th)))
    bound = peel_error_bound(pb.code, erased, z, DECODE_ITERS,
                             input_error=z_err)[:k]
    resolved = np.isfinite(bound)
    g = Mth - pb.b64
    g_err = np.where(resolved, bound + u * np.abs(g), 0.0)
    return g, resolved, g_err


def reference_thetas(pb: Problem, steps: int, depth: int = 1) -> list:
    """Plain f32 GD on the device, independent of the coded scheme:
    θ_{t+1} = θ_t − lr·(M θ_{t−depth+1} − b) (depth 1 = synchronous)."""
    @jax.jit
    def step(theta, theta_in):
        g = jnp.matmul(pb.M, theta_in, precision=HIGHEST) - pb.b
        return theta - pb.lr * g

    thetas = [jnp.zeros(pb.code.K, jnp.float32)]
    for t in range(steps):
        thetas.append(step(thetas[t], thetas[max(t - depth + 1, 0)]))
    return [np.asarray(th) for th in thetas]


def reference_tolerance(pb: Problem, thetas, depth: int = 1) -> float:
    """Bound on ‖θ_T^driver − θ_T^ref‖₂ when neither side loses a worker:
    both compute g = fl(M θ) − b with at most the per-coordinate f32 error
    of :func:`gradient_error`, and the update θ − lr·g contracts
    differences (‖I − lr·M‖₂ <= 1), so per-step bounds add up."""
    u = F32_OP_ERROR
    none = np.zeros(pb.code.N, bool)
    tol = 0.0
    for t in range(len(thetas) - 1):
        _, _, g_err = gradient_error(pb, thetas[max(t - depth + 1, 0)],
                                     none)
        tol += (2 * pb.lr * np.linalg.norm(g_err)
                + 2 * u * np.linalg.norm(np.asarray(thetas[t + 1],
                                                    np.float64)))
    return tol


def check_step(pb: Problem, theta, theta2, erased, n_unres: int,
               budget: int) -> dict:
    """One synchronous step against the host schedule solve and the exact
    gradient at the driver's own iterate."""
    check(budget == DECODE_ITERS, f"round budget {budget} != {DECODE_ITERS}")
    g, resolved, g_err = gradient_error(pb, theta, erased)
    want_unres = int((~resolved).sum())
    check(n_unres == want_unres,
          f"unresolved {n_unres} != host schedule solve {want_unres}")
    th = np.asarray(theta, np.float64)
    th2 = np.asarray(theta2, np.float64)
    check(bool(np.isfinite(th2).all()), "non-finite iterate")
    check(bool((th2[~resolved] == th[~resolved]).all()),
          "an unresolved coordinate moved (zero-fill violated)")
    step = pb.lr * np.where(resolved, g, 0.0)
    expect = th - step
    dev = np.abs(th2 - expect)
    # the coded gradient's bound, plus the f32 update θ − lr·g (lr's own
    # rounding, the product and the subtraction)
    tol = pb.lr * g_err + 2 * F32_OP_ERROR * (np.abs(step)
                                              + np.abs(expect))
    worst = float(np.max(dev - tol))
    check(worst <= 0.0, f"resolved gradient outside the peel-chain bound "
          f"by {worst!r}")
    return {"unresolved": n_unres,
            "max_dev_over_tol": float(np.max(dev / np.maximum(tol, 1e-300)))}


# ---------------------------------------------------------------- phases


def _lowers_to_kernel(dist: DistributedCodedGD, pb: Problem) -> bool:
    """Whether the driver's master program lowers to a compiled Pallas
    kernel (a ``tpu_custom_call``) rather than interpreted HLO."""
    k, N, W = pb.code.K, pb.code.N, pb.topo.n_workers
    lowered = dist._master_program.lower(
        jnp.zeros(N, jnp.float32), jnp.zeros(W, bool),
        jnp.zeros(k, jnp.float32), np.zeros(1, np.int32), dist._decode_H)
    return "tpu_custom_call" in lowered.as_text()


def run_sync(pb: Problem, config: str, q: float, steps: int, seed: int,
             mesh, clock: CompileClock, ref=None) -> dict:
    """One ``DistributedCodedGD`` configuration for ``steps`` steps with
    every check; ``config`` is a decode backend name, or ``replay`` /
    ``sharded`` for those master decodes over the sparse scheme."""
    c0 = clock.total
    if config in ("replay", "sharded"):
        scheme, master_decode = pb.base, config
    else:
        scheme = dataclasses.replace(pb.base, decode_backend=config)
        master_decode = "single"
    dist = DistributedCodedGD(scheme, pb.topo, mesh,
                              master_decode=master_decode)
    on_tpu = jax.default_backend() == "tpu"
    rec = {"phase": "A" if mesh.size == 1 else "four_chips",
           "driver": "DistributedCodedGD", "config": config,
           "master_decode": master_decode, "q": q,
           "k": pb.code.K, "N": pb.code.N, "W": pb.topo.n_workers,
           "devices": int(mesh.size),
           "device_kind": jax.devices()[0].device_kind,
           "resolved_backend": ("sparse" if master_decode != "single" else
                                resolve_backend(scheme.decode_backend,
                                                pb.code))}
    if config == "auto":
        kernel = _lowers_to_kernel(dist, pb)
        check(kernel == on_tpu,
              f"auto decode lowers to tpu_custom_call={kernel} on "
              f"{jax.default_backend()}")
        rec["tpu_custom_call"] = kernel
    if mesh.size > 1:
        devs = {s.device.id for s in dist._C_sharded.addressable_shards}
        check(len(devs) == mesh.size,
              f"worker shards on {len(devs)} devices, mesh has {mesh.size}")
        rec["worker_shard_devices"] = sorted(devs)
        if master_decode == "sharded":
            rec["check_table_shard_devices"] = sorted(
                {s.device.id
                 for s in dist._sharded_tables[0].addressable_shards})

    stragglers = WorkerStragglers(BernoulliStragglers(q), pb.topo)
    keys = jax.random.split(jax.random.PRNGKey(seed), steps)
    theta = jnp.zeros(pb.code.K, jnp.float32)
    losses, unres, ratios, step_s = [loss(pb, theta)], [], [], []
    n_straggling = []
    for t in range(steps):
        mask = np.asarray(stragglers.sample_workers(keys[t]))
        n_straggling.append(int(mask.sum()))
        t0 = time.perf_counter()
        theta2, n_unres, _, budget = dist.step(theta, mask)
        theta2.block_until_ready()
        step_s.append(round(time.perf_counter() - t0, 4))
        erased = np.asarray(pb.topo.to_symbol_erasure(mask))
        r = check_step(pb, theta, theta2, erased, n_unres, budget)
        unres.append(r["unresolved"])
        ratios.append(r["max_dev_over_tol"])
        theta = theta2
        losses.append(loss(pb, theta))
        check(losses[-1] < losses[-2],
              f"loss did not decrease at step {t}: {losses[-2]!r} -> "
              f"{losses[-1]!r}")
    if q == 0.0:
        check(max(unres) == 0, "unresolved coordinates without stragglers")
        ref_thetas, tol = ref
        dev = float(np.linalg.norm(np.asarray(theta, np.float64)
                                   - ref_thetas[-1]))
        check(dev <= tol, f"θ_T off the f32 reference by {dev!r} > {tol!r}")
        norm = float(np.linalg.norm(ref_thetas[-1]))
        rec.update(ref_dev_rel=dev / norm, rtol=tol / norm)
    rec.update(stragglers=n_straggling, unresolved=unres,
               max_dev_over_tol=max(ratios),
               loss=losses, step_s=step_s,
               compile_s=round(clock.total - c0, 3),
               peak_bytes=peak_bytes(list(mesh.devices.flat)), ok=True)
    return rec


def run_pipeline(pb: Problem, steps: int, seed: int, mesh,
                 clock: CompileClock, delay: bool) -> dict:
    """``AsyncDistributedCodedGD``, depth 2, fold window 2, sparse decode:
    without stragglers against the delayed plain reference, or under a
    delay model where late workers fold into later updates."""
    c0 = clock.total
    depth = 2
    pipe = AsyncDistributedCodedGD(pb.base, pb.topo, mesh, depth=depth,
                                   max_staleness=2, staleness_decay=0.5)
    theta0 = jnp.zeros(pb.code.K, jnp.float32)
    kw = ({"delay_model": DelayModel(tau=1.0, mu=1.0)} if delay else {})
    t0 = time.perf_counter()
    res = pipe.run(theta0, BernoulliStragglers(0.0), steps,
                   key=jax.random.PRNGKey(seed), record_thetas=True, **kw)
    res.theta.block_until_ready()
    wall = time.perf_counter() - t0
    thetas = np.asarray(res.thetas)
    check(bool(np.isfinite(thetas).all()), "non-finite pipelined iterate")
    losses = [loss(pb, theta0)] + [loss(pb, th) for th in thetas]
    check(losses[-1] < losses[0],
          f"pipelined loss did not decrease: {losses[0]!r} -> "
          f"{losses[-1]!r}")
    rec = {"phase": "B", "driver": "AsyncDistributedCodedGD",
           "config": "sparse", "depth": depth, "max_staleness": 2,
           "delay_model": delay, "k": pb.code.K, "N": pb.code.N,
           "W": pb.topo.n_workers,
           "device_kind": jax.devices()[0].device_kind,
           "unresolved": res.unresolved.tolist(),
           "resolved_late": res.resolved_late.tolist(),
           "wait_for": res.wait_for.tolist(), "loss": losses}
    if not delay:
        check(int(res.unresolved.max()) == 0,
              "unresolved coordinates without stragglers")
        ref = reference_thetas(pb, steps, depth=depth)
        tol = reference_tolerance(pb, ref, depth=depth)
        dev = float(np.linalg.norm(thetas[-1].astype(np.float64) - ref[-1]))
        check(dev <= tol, f"pipelined θ_T off the delayed f32 reference by "
              f"{dev!r} > {tol!r}")
        norm = float(np.linalg.norm(ref[-1]))
        rec.update(ref_dev_rel=dev / norm, rtol=tol / norm)
    rec.update(run_s=round(wall, 4), compile_s=round(clock.total - c0, 3),
               peak_bytes=peak_bytes(list(mesh.devices.flat)), ok=True)
    return rec


def one_chip_phases(pb: Problem, steps: int, seed: int, clock,
                    configs=("sparse", "auto", "replay")):
    """Phases A and B on one device; yields one record per run."""
    mesh = make_worker_mesh(1)
    ref = reference_thetas(pb, steps)
    ref = (ref, reference_tolerance(pb, ref))
    for q in (0.0, STRAGGLER_Q):
        for config in configs:
            yield run_sync(pb, config, q, steps, seed, mesh, clock, ref)
    for delay in (False, True):
        yield run_pipeline(pb, steps, seed, mesh, clock, delay)


def four_chip_phase(pb: Problem, steps: int, seed: int, clock,
                    n_devices: int = 4):
    """``DistributedCodedGD`` over a 4-device workers mesh, single and
    sharded master decodes, against the one-device reference."""
    mesh = make_worker_mesh(n_devices)
    ref = reference_thetas(pb, steps)
    ref = (ref, reference_tolerance(pb, ref))
    for q in (0.0, STRAGGLER_Q):
        for config in ("auto", "sharded"):
            yield run_sync(pb, config, q, steps, seed, mesh, clock, ref)


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=8192,
                    help="problem dimension k = code dimension K")
    ap.add_argument("--workers", type=int, default=32)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh path")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); "
              "this script measures the chip only", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    clock = CompileClock()
    t0 = time.perf_counter()
    pb = build_problem(args.k, args.workers, args.seed)
    print(json.dumps({"phase": "setup", "k": args.k, "N": pb.code.N,
                      "W": args.workers, "m": 4 * args.k,
                      "device_kind": devs[0].device_kind, **pb.setup,
                      "setup_s": round(time.perf_counter() - t0, 3),
                      "compile_s": round(clock.total, 3),
                      "peak_bytes": peak_bytes()}), flush=True)
    runs = (four_chip_phase(pb, args.steps, args.seed, clock)
            if args.chips == 4 else
            one_chip_phases(pb, args.steps, args.seed, clock))
    for rec in runs:
        print(json.dumps(rec), flush=True)
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
