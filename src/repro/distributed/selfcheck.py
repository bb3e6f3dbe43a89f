"""Distributed-vs-single-device parity selfcheck, runnable on any mesh.

Runs the same moment-encoded GD trajectory twice — single-device
:class:`repro.core.coded_step.Scheme2` under the lifted per-worker masks,
and :class:`repro.distributed.master.DistributedCodedGD` over the current
device mesh — and asserts the iterates match BIT FOR BIT at every step,
for every requested decode backend.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m repro.distributed.selfcheck --workers 8

Exit code 0 and a one-line "parity OK" per backend on success; an assertion
with the first diverging step otherwise.  The CI fake-8-device job and
``tests/test_distributed.py``'s subprocess test both run this module.

The additive-loss check (``--grad-agg``) is held to the decoder's value
contract instead: exact unresolved counts, sums within the peel-chain
error bound of the exact coded sum.

``--worker-encode seeded`` swaps both sides to the seeded-LDGM pipeline
(``Scheme2.build_seeded`` vs ``DistributedCodedGD(worker_encode="seeded")``):
workers hold only their slice of the generator gather tables and fuse the
encode into the matvec — parity then proves the on-the-fly worker encode is
bit-identical to the single-device seeded gather.  ``--worker-encode
seeded-fused`` goes one step further: BOTH sides run the fused Pallas
encode kernel (reference ``Scheme2.build_seeded(..., encode_fused=True)``
vs fused shard-local kernels with traced row offsets) — parity proves the
in-register index regeneration matches per shard.  ``--grad-agg`` checks the
additive-loss path instead: :class:`repro.distributed.master
.DistributedCodedAggregator` vs the single-device
:class:`repro.core.grad_agg.CodedAggregator` under the lifted worker masks.
``--pipeline`` checks the asynchronous runtime's degenerate corner:
:class:`repro.distributed.pipeline.AsyncDistributedCodedGD` at depth 1
with a zero fold window must walk the EXACT synchronous trajectory —
double buffering, donated master buffers, and the fold machinery being
armed-but-idle change no bit.
"""
from __future__ import annotations

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    BernoulliStragglers,
    CodedAggregator,
    DelayModel,
    Scheme2,
    make_regular_ldpc,
    second_moment,
)
from repro.core.decoder import F32_OP_ERROR, peel_error_bound
from repro.core.ldpc import make_seeded_ldgm
from repro.data import make_linear_problem
from repro.distributed.master import (
    DistributedCodedAggregator,
    DistributedCodedGD,
)
from repro.distributed.pipeline import AsyncDistributedCodedGD
from repro.distributed.topology import WorkerTopology, make_worker_mesh
from repro.distributed.worker import WorkerStragglers


def _build_scheme(K: int, worker_encode: str, backend: str, seed: int):
    """The shared problem + scheme of the GD parity checks: a seeded LDGM
    scheme for the seeded worker encodes (fused kernel on the reference
    side under ``seeded-fused`` — the kernel must sit on BOTH sides for
    bit-parity, since it fixes its own FMA summation order), the
    materialized regular-LDPC scheme otherwise."""
    if worker_encode in ("seeded", "seeded-fused"):
        # Seeded layered-permutation P needs K % rw == 0 and
        # p % (K // rw) == 0; (K, K//2, rw=8) satisfies both for K % 16 == 0.
        code = make_seeded_ldgm(K, K // 2, row_weight=8, seed=seed)
    else:
        code = make_regular_ldpc(K, l=3, r=6, seed=seed)
    prob = make_linear_problem(m=4 * K, k=K, seed=seed)
    mom = second_moment(prob.X, prob.y)
    if worker_encode == "materialized":
        scheme = Scheme2.build(code, mom, lr=prob.lr, decode_iters=8,
                               decode_backend=backend)
    else:
        scheme = Scheme2.build_seeded(
            code, mom, lr=prob.lr, decode_iters=8, decode_backend=backend,
            encode_fused=(worker_encode == "seeded-fused"))
    return scheme, prob


def check_parity(*, K: int = 64, n_workers: int = 8, steps: int = 6,
                 q0: float = 0.25, backend: str = "sparse",
                 master_decode: str = "single",
                 worker_encode: str = "materialized", seed: int = 0) -> int:
    """Returns the number of steps checked; raises AssertionError on the
    first diverging iterate.

    ``master_decode="sharded"`` swaps the master's decode for the
    check-tile-sharded one (:mod:`repro.distributed.sharded_decode`) while
    the single-device reference keeps decoding through the engine — the
    assertion then proves the SHARDED decode itself is bit-identical to the
    single-device decode (use ``backend="sparse"``: the sharded rounds are
    the sparse neighbor-table rounds, shard-partitioned).

    ``worker_encode="seeded"`` runs the seeded-LDGM pipeline on BOTH sides:
    the reference is the single-device ``Scheme2.build_seeded`` (per-row
    generator gather over ``y = M θ``), the distributed side shards the
    gather tables over the mesh — parity proves the fused worker-side
    encode-matvec is bit-identical to the single-device one.
    ``worker_encode="seeded-fused"`` puts the fused Pallas encode kernel on
    both sides (reference built ``encode_fused=True``; workers run the same
    kernel over their own row windows with a traced row offset).
    """
    scheme, prob = _build_scheme(K, worker_encode, backend, seed)
    code = scheme.code
    topo = WorkerTopology(n_workers, code.N)
    dist = DistributedCodedGD(scheme, topo, make_worker_mesh(),
                              master_decode=master_decode,
                              worker_encode=worker_encode)
    stragglers = WorkerStragglers(BernoulliStragglers(q0), topo)

    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, steps)
    theta_ref = jnp.zeros(K)
    theta_dist = jnp.zeros(K)
    # Jitted like the distributed step — the claim under test is that
    # DISTRIBUTION (sharded workers, per-worker erasure, gather) changes
    # nothing, so both sides must be whole-step XLA programs; an eager
    # reference differs in fused-multiply-add choices, not in placement.
    ref_step = jax.jit(scheme.step)
    for t in range(steps):
        worker_mask = stragglers.sample_workers(keys[t])
        # single-device reference: Scheme2 under the LIFTED mask
        theta_ref, _ = ref_step(theta_ref,
                                topo.to_symbol_erasure(worker_mask))
        theta_dist, _, _, _ = dist.step(theta_dist, worker_mask)
        ref, got = np.asarray(theta_ref), np.asarray(theta_dist)
        if not (ref == got).all():
            bad = int(np.argmax(ref != got))
            raise AssertionError(
                f"backend={backend} master_decode={master_decode} "
                f"worker_encode={worker_encode}: iterates diverge at step "
                f"{t}, coordinate {bad}: {ref[bad]!r} != {got[bad]!r}")
    return steps


def grad_agg_tolerance(agg: CodedAggregator, partials, erased
                       ) -> tuple[np.ndarray, np.ndarray]:
    """The exact coded sum and its value-contract tolerance for one mask.

    Returns ``(exact (dim,), tol (dim,))``: the float64 sum of the shards
    the decode recovers (zero-filled unresolved shards, Lemma 1) and the
    bound any f32 implementation must meet — the per-shard peel-chain
    bounds (:func:`repro.core.decoder.peel_error_bound`, with every worker
    symbol's own f32 encode error as input) summed over the recovered
    shards, plus the f32 summation of those shards.
    """
    code, u = agg.code, F32_OP_ERROR
    P = np.asarray(partials, np.float64)
    G = np.asarray(code.G, np.float64)
    row_weight = int((G != 0).sum(axis=1).max())
    symbols = G @ P
    enc_err = (row_weight + 1) * u * (np.abs(G) @ np.abs(P))
    bound = peel_error_bound(code, erased, symbols, agg.decode_iters,
                             input_error=enc_err)[:code.K]
    got = np.isfinite(bound[:, 0])
    exact = P[got].sum(axis=0) * agg.debias_scale
    tol = (bound[got].sum(axis=0)
           + code.K * u * np.abs(P[got]).sum(axis=0)) * agg.debias_scale
    return exact, tol


def check_grad_agg_parity(*, n_shards: int = 64, dim: int = 17,
                          n_workers: int = 8, steps: int = 4,
                          q0: float = 0.25, backend: str = "sparse",
                          seed: int = 0) -> int:
    """Additive-loss path parity: :class:`DistributedCodedAggregator` (2-D
    payload worker launch + master decode) vs the single-device
    :class:`CodedAggregator` under the lifted worker mask.  Unresolved
    counts must agree exactly and both sums must sit within
    :func:`grad_agg_tolerance` of the exact coded sum — not bit for bit:
    a worker's row-block GEMM ``G_shard @ partials`` is free to block its
    f32 sums differently from the full ``G @ partials`` (XLA:CPU does).
    Returns the number of masks checked."""
    agg = CodedAggregator.build(n_shards=n_shards, redundancy=0.5,
                                row_weight=4, seed=seed,
                                decode_backend=backend)
    topo = WorkerTopology(n_workers, agg.n_workers)
    dagg = DistributedCodedAggregator(agg, topo, make_worker_mesh())
    model = BernoulliStragglers(q0)
    key = jax.random.PRNGKey(seed)
    partials = jax.random.normal(key, (n_shards, dim))
    ref_agg = jax.jit(agg.aggregate)
    for t in range(steps):
        worker_mask = model.sample(jax.random.fold_in(key, t), n_workers)
        erased = topo.to_symbol_erasure(worker_mask)
        total_d, unres_d = dagg.aggregate(partials, worker_mask)
        total_s, unres_s = ref_agg(partials, erased)
        if int(unres_s) != int(unres_d):
            raise AssertionError(
                f"grad-agg backend={backend}: unresolved counts diverge at "
                f"mask {t}: {int(unres_s)} != {int(unres_d)}")
        exact, tol = grad_agg_tolerance(agg, partials, erased)
        for side, total in (("single-device", total_s),
                            ("distributed", total_d)):
            dev = np.abs(np.asarray(total, np.float64) - exact)
            if (dev > tol).any():
                bad = int(np.argmax(dev - tol))
                raise AssertionError(
                    f"grad-agg backend={backend} {side}: sum outside the "
                    f"peel-chain bound at mask {t}, coordinate {bad}: "
                    f"|{float(total[bad])!r} - {exact[bad]!r}| > "
                    f"{tol[bad]!r}")
    return steps


def check_pipeline_parity(*, K: int = 64, n_workers: int = 8, steps: int = 6,
                          q0: float = 0.25, backend: str = "sparse",
                          worker_encode: str = "materialized",
                          master_decode: str = "single",
                          seed: int = 0) -> int:
    """Depth-1 / zero-fold-window pipeline vs the synchronous driver.

    Both runtimes consume the same key schedule, so they realize identical
    masks (straggler-model leg) and identical delays → wait-for → cut
    decisions (delay-model leg, which exercises the telemetry-driven
    control plane shared through ``delay_step_control``).  The iterates,
    unresolved counts, round counts, and budgets must match exactly; the
    assertion names the first diverging step.  Returns total steps checked.

    ``master_decode="replay"`` puts the pattern-compiled replay decode on
    BOTH drivers (each with its own schedule cache): parity then proves
    the pipeline's plan-time schedule pre-solve and eager replay dispatch
    change no bit relative to the synchronous replay step.
    """
    scheme, prob = _build_scheme(K, worker_encode, backend, seed)
    code = scheme.code
    topo = WorkerTopology(n_workers, code.N)
    mesh = make_worker_mesh()
    theta0 = jnp.zeros(K)
    key = jax.random.PRNGKey(seed)
    checked = 0
    legs = (("straggler", BernoulliStragglers(q0), None),
            ("delay", None, DelayModel(tau=1.0, mu=1.0)))
    for name, model, delay_model in legs:
        sync = DistributedCodedGD(scheme, topo, mesh,
                                  master_decode=master_decode,
                                  worker_encode=worker_encode)
        pipe = AsyncDistributedCodedGD(scheme, topo, mesh, depth=1,
                                       max_staleness=0,
                                       master_decode=master_decode,
                                       worker_encode=worker_encode)
        rs = sync.run(theta0, model, steps, key=key,
                      theta_star=prob.theta_star, delay_model=delay_model)
        rp = pipe.run(theta0, model, steps, key=key,
                      theta_star=prob.theta_star, delay_model=delay_model,
                      record_thetas=True)
        ref, got = np.asarray(rs.theta), np.asarray(rp.theta)
        if not (ref == got).all():
            bad = int(np.argmax(ref != got))
            raise AssertionError(
                f"pipeline backend={backend} worker_encode={worker_encode} "
                f"master_decode={master_decode} "
                f"leg={name}: final iterates diverge at coordinate {bad}: "
                f"{ref[bad]!r} != {got[bad]!r}")
        for field in ("unresolved", "rounds", "budgets", "wait_for"):
            a, b = getattr(rs, field), getattr(rp, field)
            if not (np.asarray(a) == np.asarray(b)).all():
                t = int(np.argmax(np.asarray(a) != np.asarray(b)))
                raise AssertionError(
                    f"pipeline backend={backend} leg={name}: {field} "
                    f"diverges at step {t}: {a[t]!r} != {b[t]!r}")
        checked += steps
    return checked


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--K", type=int, default=64)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--q0", type=float, default=0.25)
    ap.add_argument("--backends", default="dense,sparse,pallas",
                    help="comma-separated decode backends to check")
    ap.add_argument("--master-decode", default="single",
                    choices=["single", "sharded", "replay"],
                    help="sharded = the master decode itself runs over the "
                         "mesh (check tiles partitioned; reference stays "
                         "the single-device sparse decode); replay = the "
                         "pattern-compiled schedule replay with a cross-step "
                         "cache (reference likewise the single-device "
                         "sparse decode)")
    ap.add_argument("--worker-encode", default="materialized",
                    choices=["materialized", "seeded", "seeded-fused"],
                    help="seeded = workers hold only generator gather "
                         "tables and fuse encode into the matvec "
                         "(reference is the single-device seeded scheme); "
                         "seeded-fused = the fused Pallas encode kernel on "
                         "both sides, indices regenerated in-register")
    ap.add_argument("--grad-agg", action="store_true",
                    help="check the additive-loss DistributedCodedAggregator "
                         "against the single-device CodedAggregator instead "
                         "of the moment-encoded GD step")
    ap.add_argument("--pipeline", action="store_true",
                    help="check the depth-1 / zero-fold-window asynchronous "
                         "pipeline against the synchronous driver (straggler "
                         "and delay-model legs) instead of the GD step")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable result: one JSON object on stdout "
                         "({ok, devices, workers, checks: [...]}) with "
                         "per-check pass/fail instead of human parity lines; "
                         "failures are collected (exit 1), not raised")
    ap.add_argument("--obs-out", default=None, metavar="PATH",
                    help="export obs metrics JSONL (+ .trace.json spans) "
                         "from the instrumented parity runs to PATH")
    args = ap.parse_args(argv)
    from repro.obs import ObsSession
    session = ObsSession.start(args.obs_out)
    n_dev = jax.device_count()

    # (kind, backend, extra-detail, runner, human success line) per check —
    # one uniform loop so --json and the human output cannot drift.
    checks = []
    if args.pipeline:
        # Replay overrides the scheme backend on both drivers, so one
        # sparse-scheme run is the whole matrix (as with sharded below).
        backends = (["sparse"] if args.master_decode == "replay"
                    else args.backends.split(","))
        for backend in backends:
            checks.append((
                "pipeline", backend,
                {"worker_encode": args.worker_encode,
                 "master_decode": args.master_decode},
                functools.partial(check_pipeline_parity, K=args.K,
                                  n_workers=args.workers, steps=args.steps,
                                  q0=args.q0, backend=backend,
                                  worker_encode=args.worker_encode,
                                  master_decode=args.master_decode),
                lambda steps, backend=backend: (
                    f"parity OK: pipeline backend={backend} "
                    f"worker_encode={args.worker_encode} "
                    f"master_decode={args.master_decode} W={args.workers} "
                    f"devices={n_dev} steps={steps} "
                    "(bit-identical iterates)")))
    elif args.grad_agg:
        for backend in args.backends.split(","):
            checks.append((
                "grad-agg", backend, {},
                functools.partial(check_grad_agg_parity, n_shards=args.K,
                                  n_workers=args.workers, steps=args.steps,
                                  q0=args.q0, backend=backend),
                lambda steps, backend=backend: (
                    f"parity OK: grad-agg backend={backend} W={args.workers} "
                    f"devices={n_dev} masks={steps} "
                    "(sums within the peel-chain bound)")))
    else:
        if args.master_decode in ("sharded", "replay"):
            # The sharded rounds ARE the sparse neighbor-table rounds (and
            # replay reproduces the sparse flooding arithmetic exactly), so
            # the bit-parity reference is the sparse single-device decode.
            backends = ["sparse"]
        else:
            backends = args.backends.split(",")
        for backend in backends:
            checks.append((
                "gd-step", backend,
                {"master_decode": args.master_decode,
                 "worker_encode": args.worker_encode},
                functools.partial(check_parity, K=args.K,
                                  n_workers=args.workers, steps=args.steps,
                                  q0=args.q0, backend=backend,
                                  master_decode=args.master_decode,
                                  worker_encode=args.worker_encode),
                lambda steps, backend=backend: (
                    f"parity OK: backend={backend} "
                    f"master_decode={args.master_decode} "
                    f"worker_encode={args.worker_encode} W={args.workers} "
                    f"devices={n_dev} steps={steps} "
                    "(bit-identical iterates)")))

    records, ok_all = [], True
    try:
        for kind, backend, detail, run, ok_line in checks:
            rec = {"kind": kind, "backend": backend, **detail}
            try:
                steps = run()
            except AssertionError as e:
                if not args.json:
                    raise     # legacy behavior: fail loudly on first diverge
                rec.update(ok=False, error=str(e))
                ok_all = False
            else:
                rec.update(ok=True, steps=int(steps))
                if not args.json:
                    print(ok_line(steps))
            records.append(rec)
    finally:
        # ObsSession prints its status to stderr, keeping --json stdout pure.
        session.finish()
    if args.json:
        print(json.dumps({"ok": ok_all, "devices": n_dev,
                          "workers": args.workers, "checks": records}))
    return 0 if ok_all else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
