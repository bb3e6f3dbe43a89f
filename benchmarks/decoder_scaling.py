"""LDPC decoding complexity & adaptivity (Section 3 claims), plus the
decode-backend scaling comparison that tracks the sparse/fused-kernel
hillclimb across PRs.

Sections:

  1. backend scaling — dense vs sparse (neighbor-table) vs fused-Pallas
     fixed-D decode latency at growing N, with achieved FLOP/s.  Emits the
     machine-readable ``BENCH_decoder_scaling.json`` (repo root by default)
     so the perf trajectory is comparable across PRs.
  2. batched decode over B INDEPENDENT erasure patterns (the engine's
     serving axis): per-query cost of one batched launch (vmapped-sparse /
     batched-Pallas) vs B sequential single-pattern decodes, B ∈
     {1, 8, 64, 256}.
  2b. mixed light/heavy straggler SERVING sweep — continuous admission
     (per-slot adaptive decode, slots retire/refill independently, chunked
     round budgets — the policy behind
     ``serving.coded_queries.CodedQueryBatcher(mode="continuous")``) vs
     lockstep waves (every wave pays the worst-case fixed round budget).
     Simulated on the decode path itself so the measured quantity is the
     mean per-query DECODE cost; ``speedup_vs_lockstep`` is a same-run
     ratio (both policies timed in one run on one machine), which is what
     ``check_regression.py`` gates.
  3. the adaptive peeling decoder's round count AND cost track the number of
     realized stragglers (few stragglers -> 1-2 rounds -> "decoding effort
     auto-adjusts");
  4. decode quality (|unresolved|) is monotone in the fixed round budget D;
  5. LDPC peeling cost vs MDS/Vandermonde least-squares recovery cost — the
     paper's low-complexity-decode argument (O(edges) vs O(w·K²) flops).
  6. LARGE-N sweep (schema v5): decode latency past the whole-H-in-VMEM
     regime, N up to 16384 — dense vs sparse everywhere both fit, plus the
     check-axis-TILED fused kernel (``backend="pallas_tiled"``) where it is
     timeable (compiled on TPU at every N; off-TPU a small-N interpret-mode
     correctness record only, flagged).  ``speedup_vs_dense`` is the
     same-run ratio ``check_regression.py --sections large_n`` gates.
     Codes are built PARITY-ONLY (``make_parity_only_ldpc``) — the decode
     trajectory never needs a generator, and the systematic solve is the
     construction bottleneck past N ≈ 4096.

  7. SEEDED sweep (schema v6): the seed-regenerated kernel
     (``backend="pallas_seeded"``) vs the check-axis-tiled one.  Per N up
     to 32768: the MODELED per-decode operand HBM traffic of both (tiled
     streams the whole padded (p, N) f32 H from HBM every round; seeded
     regenerates each tile in-register and streams only the payload) and
     the same-run ``traffic_ratio_vs_tiled`` that
     ``check_regression.py --sections seeded`` gates (≥10× at N=16384).
     At N=2048 both kernels are also TIMED (interpret mode off-TPU, a
     same-run ``wallclock_ratio_vs_tiled``) with a bit-identical-values
     trajectory tripwire; one lower-only record proves the seeded kernel
     lowers at N=262144, where even materializing H (128 GiB f32) is
     infeasible — there is nothing to compare against there.

  8. SEEDED-GATHER sweep (schema v8): the edge-proportional gather round
     (``seeded_mode="gather"``) vs the dense regenerated-tile round inside
     the same seeded kernel.  Per N up to 32768: the MODELED per-round
     FLOPs of both (the :mod:`repro.core.hwcaps` expressions behind
     ``seeded_mode="auto"``: the dense round contracts a ``p_pad × n_pad``
     tile per payload lane; the gather round touches only the r generated
     edges per check row plus the per-layer inverse-permutation merge) and
     the same-run ``flops_ratio_vs_dense_tile`` that
     ``check_regression.py --sections seeded_gather`` gates (hard ≥8×
     floor at N=16384).  At N=2048 both modes are also TIMED (interpret
     off-TPU) with a trajectory tripwire: erasure masks bit-identical,
     never-erased values bit-equal (resolved VALUES agree only up to f32
     summation order — the two rounds sum in different shapes).

  9. REPLAY sweep (schema v10): pattern-compiled peeling on a RECURRING
     straggler stream at N = 8192 — the ``backend="replay"`` +
     :class:`repro.core.schedule_cache.ScheduleCache` serving loop vs the
     flooding sparse adaptive decode, per query.  Records the MODELED work
     ratio (flooding touches every check row's r_max edges every round;
     replay touches only the schedule's resolving rows once), the TIMED
     same-run ``cache_hit_speedup_vs_sparse`` over the warm-cache stream,
     the realized ``schedule_cache_hit_rate`` of a cold cache over the
     same stream (read back from the obs ``sched_cache.hit_rate`` gauge),
     and a bit-identity tripwire: every pattern's replay must reproduce
     the flooding decode's values and erasure trajectory exactly.
     ``check_regression.py --sections replay`` gates the speedup (hard
     ≥2× floor), the hit rate (≥0.8), and the tripwire.

Forcing ``--backend pallas`` (CLI) past the VMEM limit no longer crashes:
``benchmarks.common.resolve_bench_backend`` fails over with a clear message
(to "pallas_tiled" on TPU, "sparse" off-TPU), and the quick CI run
exercises that path; ``--backend pallas_seeded`` on the sweep's unseeded
codes fails over the same way (the seed is a property of the CODE).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import print_table, resolve_bench_backend
from repro.core import FixedCountStragglers, make_regular_ldpc, peel_decode, \
    peel_decode_adaptive, peel_decode_batch, peel_decode_batch_adaptive
from repro.core.ldpc import make_parity_only_ldpc
from repro.serving.slot_lifecycle import SlotPool

BENCH_JSON = Path(__file__).resolve().parents[1] / "BENCH_decoder_scaling.json"

# The fused kernel runs in interpret mode on CPU — orders of magnitude
# slower than compiled, so its latency is NOT comparable; measure it only
# at small N off-TPU to keep the benchmark fast, and flag it in the JSON.
_PALLAS_CPU_MAX_N = 256


def _median_seconds(fn, *args, reps):
    fn(*args)[0].block_until_ready()  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args)[0].block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run_backend_scaling(*, Ks=(64, 256, 512, 1024, 2048), V=8, D=8, q=0.25,
                        reps=5):
    """Fixed-D decode latency per backend; returns (table_rows, json_records)."""
    on_tpu = jax.default_backend() == "tpu"
    rows, records = [], []
    for K in Ks:
        code = make_regular_ldpc(K, l=3, r=6, seed=0)
        N, p = code.N, code.p
        r_max = code.check_idx.shape[1]
        rng = np.random.default_rng(K)
        cw = jnp.asarray(code.encode(rng.standard_normal((K, V))), jnp.float32)
        erased = jnp.asarray(rng.random(N) < q)
        rx = jnp.where(erased[:, None], 0.0, cw)

        backends = ["dense", "sparse"]
        if on_tpu or N <= _PALLAS_CPU_MAX_N:
            backends.append("pallas")

        t_dense = None
        for backend in backends:
            fn = jax.jit(
                lambda v, e, b=backend: peel_decode(code, v, e, D, backend=b
                                                    ).values)
            t = _median_seconds(lambda v, e: (fn(v, e),), rx, erased,
                                reps=reps)
            if backend == "dense":
                t_dense = t
            # Arithmetic actually performed per decode by this backend:
            # dense touches the full (p, N) H thrice per round (counted once
            # as the dominating 2·p·N matmul per payload+mask column);
            # sparse/pallas-equivalent useful work is edge-proportional.
            if backend == "dense":
                work = 2.0 * p * N * (V + 1) * D
            else:
                work = 2.0 * p * r_max * (V + 1) * D
            rec = {
                "backend": backend,
                "N": N, "K": K, "p": p, "V": V, "D": D,
                "erasure_q": q,
                "median_s": t,
                "per_round_us": t / D * 1e6,
                "work_flops": work,
                "achieved_gflops": work / t / 1e9,
                "speedup_vs_dense": (t_dense / t) if t_dense else 1.0,
                "interpret_mode": backend == "pallas" and not on_tpu,
                "single_kernel_launch": backend == "pallas",
            }
            records.append(rec)
            rows.append([N, K, backend, f"{t * 1e6:.0f}",
                         f"{t / D * 1e6:.1f}",
                         f"{rec['achieved_gflops']:.3f}",
                         f"{rec['speedup_vs_dense']:.2f}x"])
    return rows, records


def run_batched_scaling(*, Ks=(64, 256, 1024), Bs=(1, 8, 64, 256), D=8,
                        q=0.25, reps=5):
    """Per-query cost: ONE batched decode of B patterns vs B sequential
    single-pattern decodes (same backend — the honest baseline is the
    FASTEST single-pattern decode, i.e. sparse).  The batched-sparse mode is
    the scatter-free batch-major round (``peel_round_sparse_batch``); the
    batched-Pallas mode is the one-launch grid-over-batch kernel (interpret
    mode off-TPU, so it is only timed at small N there).  Returns
    (table_rows, json_records); ``speedup_vs_sequential`` is vs
    sequential-sparse.
    """
    on_tpu = jax.default_backend() == "tpu"
    rows, records = [], []
    for K in Ks:
        code = make_regular_ldpc(K, l=3, r=6, seed=0)
        N = code.N
        rng = np.random.default_rng(K)
        for B in Bs:
            msgs = rng.standard_normal((B, K))
            cw = jnp.asarray((code.G @ msgs.T).T, jnp.float32)  # (B, N)
            erased = jnp.asarray(rng.random((B, N)) < q)
            rx = jnp.where(erased, 0.0, cw)

            # sequential baseline: B separate single-pattern launches
            single = jax.jit(
                lambda v, e: peel_decode(code, v, e, D, backend="sparse").values)
            single(rx[0], erased[0]).block_until_ready()  # compile
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for i in range(B):
                    single(rx[i], erased[i]).block_until_ready()
                ts.append(time.perf_counter() - t0)
            t_seq = float(np.median(ts))

            modes = {"batched-sparse": "sparse"}
            if on_tpu or N <= _PALLAS_CPU_MAX_N:
                modes["batched-pallas"] = "pallas"
            t_per_mode = {}
            for mode, backend in modes.items():
                fn = jax.jit(lambda v, e, b=backend: peel_decode_batch(
                    code, v, e, D, backend=b).values)
                t_per_mode[mode] = _median_seconds(
                    lambda v, e: (fn(v, e),), rx, erased, reps=reps)

            base = {"N": N, "K": K, "B": B, "D": D, "erasure_q": q,
                    "jax_backend": jax.default_backend()}
            records.append({**base, "mode": "sequential-sparse",
                            "median_s": t_seq,
                            "per_query_us": t_seq / B * 1e6,
                            "speedup_vs_sequential": 1.0,
                            "interpret_mode": False})
            rows.append([N, K, B, "sequential-sparse",
                         f"{t_seq / B * 1e6:.0f}", "1.00x"])
            for mode, t in t_per_mode.items():
                records.append({**base, "mode": mode, "median_s": t,
                                "per_query_us": t / B * 1e6,
                                "speedup_vs_sequential": t_seq / t,
                                "interpret_mode": mode == "batched-pallas"
                                and not on_tpu})
                rows.append([N, K, B, mode, f"{t / B * 1e6:.0f}",
                             f"{t_seq / t:.2f}x"])
    return rows, records


def _serve_lockstep(code, rx, erased, *, B, budget):
    """Wave policy: ONE fixed-budget batched decode per wave of B queries
    (partial final wave padded with clean no-op slots).  Returns a callable
    running the whole queue once, plus the launch count."""
    N = code.N
    fn = jax.jit(lambda v, e: peel_decode_batch(
        code, v, e, budget, backend="sparse").values)
    nq = rx.shape[0]
    pad = (-nq) % B
    rx_p = np.concatenate([rx, np.zeros((pad, N), np.float32)])
    er_p = np.concatenate([erased, np.zeros((pad, N), bool)])
    waves = [(jnp.asarray(rx_p[i:i + B]), jnp.asarray(er_p[i:i + B]))
             for i in range(0, nq + pad, B)]

    def serve():
        for v, e in waves:
            fn(v, e).block_until_ready()

    return serve, len(waves)


def serve_continuous(code, rx, erased, *, B, budget, chunk,
                     backend="sparse"):
    """Continuous admission simulated on the decode path: a pool of B slots
    advances by at most ``chunk`` per-slot adaptive rounds per launch;
    converged / budget-exhausted slots retire and refill FIFO — the
    ``CodedQueryBatcher(mode="continuous")`` slot lifecycle, minus the
    worker matvec and epilogue that both policies pay once per query (so
    the measured quantity is pure DECODE cost, the paper's adaptivity
    claim).  The lifecycle itself (admission order, budget chunking,
    retire condition) is the SHARED ``serving.slot_lifecycle.SlotPool``
    state machine — the same object the batcher drives, so the two can no
    longer drift apart; ``benchmarks/distributed_scaling`` reuses this
    driver for the master's decode-stream serving.  Returns a callable
    running the whole queue once and a stats dict (filled per run)."""
    N = code.N
    nq = rx.shape[0]
    def _launch(v, e, bu):
        dec = peel_decode_batch_adaptive(code, v, e, backend=backend,
                                         budgets=bu)
        # per-slot unresolved counts on device: host only pulls (B,) stats
        return dec.values, dec.erased, dec.rounds_used, dec.erased.sum(axis=1)

    launch = jax.jit(_launch)
    # fixed-size refill (unused rows carry the drop sentinel B) so varying
    # admission counts reuse ONE compilation
    refill = jax.jit(
        lambda v, e, idx, nv, ne: (v.at[idx].set(nv, mode="drop"),
                                   e.at[idx].set(ne, mode="drop")))
    stats = {"launches": 0, "launch_rounds": 0, "slot_rounds": 0}

    def serve():
        # slot state stays DEVICE-RESIDENT across launches (free slots get
        # budget 0, so the decode passes their rows through untouched and
        # the outputs can be carried wholesale); the host sees only (B,)
        # stats vectors for the retire/refill decisions, which live in the
        # shared SlotPool.
        pool = SlotPool(B, budget, chunk)
        vals = jnp.zeros((B, N), jnp.float32)
        er = jnp.zeros((B, N), bool)
        nxt = done = launches = launch_rounds = slot_rounds = 0
        while done < nq:
            fill = pool.free_slots()[: nq - nxt]
            if fill:
                idx = np.full((B,), B, np.int32)   # sentinel rows: dropped
                nv = np.zeros((B, N), np.float32)
                ne = np.zeros((B, N), bool)
                for j, s in enumerate(fill):
                    pool.admit(s, nxt + j)         # owner = query index
                    idx[j] = s
                    nv[j] = rx[nxt + j]
                    ne[j] = erased[nxt + j]
                nxt += len(fill)
                vals, er = refill(vals, er, jnp.asarray(idx),
                                  jnp.asarray(nv), jnp.asarray(ne))
            occupied = pool.occupied
            budgets = pool.launch_budgets()
            vals, er, rounds_d, unres_d = launch(
                vals, er, jnp.asarray(budgets))
            launches += 1
            rounds = np.asarray(rounds_d)
            unres = np.asarray(unres_d)
            # wall-cost proxy: the launch's while_loop runs until its
            # slowest active slot stops; work proxy: per-slot rounds spent.
            launch_rounds += int(rounds.max(initial=0))
            slot_rounds += int(rounds[occupied].sum())
            done += len(pool.account(rounds, unres))
        stats["launches"] = launches
        stats["launch_rounds"] = launch_rounds
        stats["slot_rounds"] = slot_rounds

    return serve, stats


def run_serving_sweep(*, K=1024, B=64, n_queries=320, heavy_frac=0.15,
                      light_q=0.08, heavy_q=0.42, budget=32, chunk=4,
                      reps=3, seed=0):
    """Mixed light/heavy straggler serving: continuous vs lockstep.

    A stream of ``n_queries`` coded queries, ``heavy_frac`` of them with
    near-threshold erasure rates (many peeling rounds to converge) and the
    rest light (1-2 rounds).  Lockstep waves pay the worst-case ``budget``
    rounds for every wave; continuous admission lets each slot stop at its
    own fixpoint and refill, so the mean per-query decode cost tracks the
    REALIZED straggler mix.  Returns (table_rows, json_records);
    ``speedup_vs_lockstep`` is the same-run per-query cost ratio.
    """
    code = make_regular_ldpc(K, l=3, r=6, seed=seed)
    N = code.N
    rng = np.random.default_rng(seed)
    msgs = rng.standard_normal((n_queries, K))
    cws = (code.G @ msgs.T).T.astype(np.float32)
    heavy = rng.random(n_queries) < heavy_frac
    qs = np.where(heavy, heavy_q, light_q)
    erased = rng.random((n_queries, N)) < qs[:, None]
    rx = np.where(erased, 0.0, cws)

    serve_ls, n_waves = _serve_lockstep(code, rx, erased, B=B, budget=budget)
    serve_ct, ct_stats = serve_continuous(code, rx, erased, B=B,
                                           budget=budget, chunk=chunk)
    results = {}
    for mode, serve in (("lockstep", serve_ls), ("continuous", serve_ct)):
        serve()  # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            serve()
            ts.append(time.perf_counter() - t0)
        results[mode] = float(np.median(ts))

    base = {"N": N, "K": K, "B": B, "n_queries": n_queries,
            "heavy_frac": heavy_frac, "light_q": light_q, "heavy_q": heavy_q,
            "budget": budget, "chunk": chunk,
            "jax_backend": jax.default_backend()}
    speedup = results["lockstep"] / results["continuous"]
    rows, records = [], []
    for mode, extra in (
            ("lockstep", {"launches": n_waves,
                          "launch_rounds": n_waves * budget,
                          "slot_rounds": n_waves * B * budget,
                          "speedup_vs_lockstep": 1.0}),
            ("continuous", {"launches": ct_stats["launches"],
                            "launch_rounds": ct_stats["launch_rounds"],
                            "slot_rounds": ct_stats["slot_rounds"],
                            "speedup_vs_lockstep": speedup})):
        t = results[mode]
        records.append({**base, "mode": mode, "median_s": t,
                        "per_query_us": t / n_queries * 1e6, **extra})
        rows.append([N, B, mode, extra["launches"], extra["launch_rounds"],
                     f"{t / n_queries * 1e6:.0f}",
                     f"{extra['speedup_vs_lockstep']:.2f}x"])
    return rows, records


def run_large_n_sweep(*, Ns=(2048, 4096, 8192, 16384), D=8, q=0.25, reps=3,
                      dense_max_n=16384, tiled_cpu_max_n=2048,
                      forced_backend: str | None = None):
    """Decode latency PAST the whole-H-in-VMEM regime (the tiled path's
    reason to exist).  Per N: the dense reference (kept through
    ``dense_max_n`` = the full sweep — its (p, N) f32 operand is ~512 MiB
    at N = 16384, the denominator every N's gate needs), sparse (the
    scalable CPU path, every N), and the check-axis-tiled fused kernel —
    timed compiled on TPU at every N; off-TPU one interpret-mode record at
    ``tiled_cpu_max_n`` only, run for trajectory parity and flagged
    ``interpret_mode`` (skipped by the gate, like every interpret record).
    The same-run ``speedup_vs_dense`` is what CI gates
    (``--sections large_n``).

    ``forced_backend`` exercises the VMEM-failover bugfix: the requested
    backend is resolved through ``resolve_bench_backend`` per N and the
    failover message (if any) is printed instead of crashing.
    """
    on_tpu = jax.default_backend() == "tpu"
    rows, records = [], []
    for K in (n // 2 for n in Ns):
        code = make_parity_only_ldpc(K, l=3, r=6, seed=0)
        N, p = code.N, code.p
        r_max = code.check_idx.shape[1]
        rng = np.random.default_rng(N)
        # The trajectory depends only on H and the mask — any payload does
        # (these codes are parity-only; there is no generator to encode with).
        vals = jnp.asarray(rng.standard_normal(N), jnp.float32)
        erased = jnp.asarray(rng.random(N) < q)
        rx = jnp.where(erased, 0.0, vals)

        backends = []
        if forced_backend:
            backend, msg = resolve_bench_backend(code, forced_backend)
            if msg:
                print(f"[large_n N={N}] {msg}")
            backends.append(backend)
        else:
            if N <= dense_max_n:
                backends.append("dense")
            backends.append("sparse")
            if on_tpu or N <= tiled_cpu_max_n:
                backends.append("pallas_tiled")

        t_dense = None
        ref_erased = None
        for backend in backends:
            # bv=8: scalar payloads need 8 lanes, not the default 128
            # (ignored by dense/sparse; keeps the interpret record cheap).
            # ONE jitted decode serves both the timing (values) and the
            # trajectory tripwire (erased) — no second compile/execute.
            fn = jax.jit(lambda v, e, b=backend: tuple(peel_decode(
                code, v, e, D, backend=b, bv=8)[:2]))
            t = _median_seconds(lambda v, e: fn(v, e), rx, erased,
                                reps=reps)
            if backend == "dense":
                t_dense = t
            # trajectory spot-check: every backend must land on the same
            # unresolved set (bit-identical masks are the tiled path's
            # correctness claim; tests prove it exhaustively, the bench
            # keeps a tripwire on the exact configs it times)
            got_erased = np.asarray(fn(rx, erased)[1])
            if ref_erased is None:
                ref_erased = got_erased
            elif (got_erased != ref_erased).any():
                raise AssertionError(
                    f"large_n N={N}: backend={backend} erasure trajectory "
                    "diverged from the first backend's")
            work = (2.0 * p * N * 2 * D if backend == "dense"
                    else 2.0 * p * r_max * 2 * D)
            interp = backend in ("pallas", "pallas_tiled") and not on_tpu
            rec = {
                "backend": backend, "N": N, "K": K, "p": p, "D": D,
                "erasure_q": q, "median_s": t,
                "per_round_us": t / D * 1e6,
                "achieved_gflops": work / t / 1e9,
                "speedup_vs_dense": (t_dense / t) if t_dense else None,
                "interpret_mode": interp,
                "forced_backend": forced_backend,
                "jax_backend": jax.default_backend(),
            }
            records.append(rec)
            rows.append([N, K, backend, f"{t * 1e6:.0f}",
                         f"{t / D * 1e6:.1f}",
                         (f"{rec['speedup_vs_dense']:.2f}x"
                          if rec["speedup_vs_dense"] else "-"),
                         "interp" if interp else ""])
    return rows, records


def _decode_operand_bytes(N: int, D: int, *, bp: int, bv: int,
                          seeded: bool) -> float:
    """Modeled per-decode operand HBM traffic (bytes) of the fused kernels.

    Both kernels hold the payload in VMEM across all D rounds (one grid
    pass over the V axis): payload traffic is the one-time load + store of
    the padded ``(n_pad, bv)`` values and ``(n_pad, 1)`` erasure columns.
    The TILED kernel additionally DMAs the whole padded ``(p_pad, n_pad)``
    f32 parity-check matrix from HBM EVERY round (check tiles of height
    ``bp``); the SEEDED kernel regenerates those tiles in-register from
    ``(seed, row)`` — zero H bytes.  This is the memory wall the seeded
    construction removes, and the quantity the regression gate tracks.
    """
    p = N // 2                       # the sweep's rate-1/2 shapes
    n_pad = N + (-N) % 128
    p_pad = p + (-p) % bp
    payload = 2 * 4.0 * (n_pad * bv + n_pad)     # in + out, values + erased
    h_stream = 0.0 if seeded else float(D) * p_pad * n_pad * 4.0
    return payload + h_stream


def run_seeded_sweep(*, Ns=(2048, 4096, 8192, 16384, 32768), D=8, q=0.25,
                     reps=3, timed_n=2048, lower_only_n=262144, bv=8):
    """Seeded vs tiled fused decode: modeled operand traffic at every N,
    wall-clock + trajectory tripwire where timeable, and a lower-only
    feasibility record at an N where H cannot be materialized at all.

    Returns (table_rows, json_records).  ``traffic_ratio_vs_tiled`` (tiled
    bytes / seeded bytes, same model both sides) is gated by
    ``check_regression.py --sections seeded`` — including the hard ≥10×
    floor at N=16384.  The timed record at ``timed_n`` runs BOTH kernels on
    one seeded code (``make_seeded_ldpc`` materializes H exactly so the
    tiled reference exists) and asserts bit-identical values and erasure
    trajectories — the seeded kernel's summation is tile-shaped like the
    tiled one's, so even the f32 values must match bit for bit.
    """
    from repro.core.ldpc import make_seeded_ldpc, seeded_structure

    on_tpu = jax.default_backend() == "tpu"
    bp = 128
    rows, records = [], []
    for N in Ns:
        tiled_b = _decode_operand_bytes(N, D, bp=bp, bv=bv, seeded=False)
        seeded_b = _decode_operand_bytes(N, D, bp=bp, bv=bv, seeded=True)
        rec = {
            "N": N, "D": D, "bp": bp, "bv": bv, "erasure_q": q,
            "modeled_tiled_bytes": tiled_b,
            "modeled_seeded_bytes": seeded_b,
            "traffic_ratio_vs_tiled": tiled_b / seeded_b,
            "timed": False,
            "jax_backend": jax.default_backend(),
        }
        timed = N == timed_n and (on_tpu or N <= 2048)
        if timed:
            code = make_seeded_ldpc(N // 2, l=4, r=8, seed=0)
            assert code.N == N, (code.N, N)
            rng = np.random.default_rng(N)
            vals = jnp.asarray(rng.standard_normal(N), jnp.float32)
            erased = jnp.asarray(rng.random(N) < q)
            rx = jnp.where(erased, 0.0, vals)
            ts, outs = {}, {}
            for backend in ("pallas_tiled", "pallas_seeded"):
                fn = jax.jit(lambda v, e, b=backend: tuple(peel_decode(
                    code, v, e, D, backend=b, bp=bp, bv=bv)[:2]))
                ts[backend] = _median_seconds(lambda v, e: fn(v, e), rx,
                                              erased, reps=reps)
                outs[backend] = tuple(np.asarray(x) for x in fn(rx, erased))
            # tripwire: same tile-shaped summation → bit-identical VALUES,
            # not just the same erasure trajectory
            if (outs["pallas_seeded"][0] != outs["pallas_tiled"][0]).any() \
                    or (outs["pallas_seeded"][1]
                        != outs["pallas_tiled"][1]).any():
                raise AssertionError(
                    f"seeded N={N}: decode diverged from pallas_tiled on "
                    "the same code (values or erasure trajectory)")
            rec.update({
                "timed": True,
                "median_s_tiled": ts["pallas_tiled"],
                "median_s_seeded": ts["pallas_seeded"],
                "wallclock_ratio_vs_tiled":
                    ts["pallas_seeded"] / ts["pallas_tiled"],
                "interpret_mode": not on_tpu,
            })
        records.append(rec)
        rows.append([N, f"{tiled_b / 2**20:.1f}", f"{seeded_b / 2**20:.3f}",
                     f"{rec['traffic_ratio_vs_tiled']:.0f}x",
                     (f"{rec['wallclock_ratio_vs_tiled']:.2f}x"
                      if timed else "-"),
                     "interp" if timed and not on_tpu else ""])

    # Feasibility: the seeded kernel LOWERS at an N where the (p, N) f32 H
    # is 128 GiB — no materialized backend can even be constructed there.
    spec = seeded_structure(lower_only_n // 2, lower_only_n, 8, 0)
    from repro.kernels.ldpc_peel import peel_decode_seeded_pallas
    fn = jax.jit(lambda v, e: peel_decode_seeded_pallas(
        spec, v, e, D, bp=512, bv=bv))
    lowered = fn.lower(
        jax.ShapeDtypeStruct((lower_only_n,), jnp.float32),
        jax.ShapeDtypeStruct((lower_only_n,), jnp.bool_))
    del lowered
    h_bytes = (lower_only_n // 2) * lower_only_n * 4.0
    records.append({
        "N": lower_only_n, "D": D, "mode": "lower-only", "lower_ok": True,
        "h_bytes_if_materialized": h_bytes,
        "jax_backend": jax.default_backend(),
    })
    rows.append([lower_only_n, f"(H would be {h_bytes / 2**30:.0f} GiB)",
                 "seed-only", "-", "lowered OK", ""])
    return rows, records


def run_seeded_gather_sweep(*, Ns=(2048, 4096, 8192, 16384, 32768), D=8,
                            V=8, q=0.25, reps=3, timed_n=2048, bp=128):
    """Gather vs dense-tile seeded rounds: modeled per-round FLOPs at every
    N, wall-clock + trajectory tripwire where timeable.

    Returns (table_rows, json_records).  ``flops_ratio_vs_dense_tile``
    (dense FLOPs / gather FLOPs, the same :mod:`repro.core.hwcaps` model
    ``seeded_mode="auto"`` dispatches on) is gated by
    ``check_regression.py --sections seeded_gather`` — including the hard
    ≥8× floor at N=16384.  The timed record at ``timed_n`` runs BOTH modes
    on one seeded code and asserts the bit-exact part of the contract:
    identical erasure trajectories and untouched never-erased values
    (resolved values agree to f32 summation order only — the dense round
    contracts over N, the gather round sums r edges per row).
    """
    from repro.core.hwcaps import (seeded_dense_round_flops,
                                   seeded_gather_round_flops)
    from repro.core.ldpc import make_seeded_ldpc, seeded_structure

    on_tpu = jax.default_backend() == "tpu"
    rows, records = [], []
    for N in Ns:
        spec = seeded_structure(N // 2, N, 8, 0)
        dense_f = seeded_dense_round_flops(spec, V, bp=bp)
        gather_f = seeded_gather_round_flops(spec, V, bp=bp)
        rec = {
            "N": N, "D": D, "V": V, "bp": bp, "erasure_q": q,
            "modeled_dense_tile_flops_per_round": dense_f,
            "modeled_gather_flops_per_round": gather_f,
            "flops_ratio_vs_dense_tile": dense_f / gather_f,
            "timed": False,
            "jax_backend": jax.default_backend(),
        }
        timed = N == timed_n and (on_tpu or N <= 2048)
        if timed:
            code = make_seeded_ldpc(N // 2, l=4, r=8, seed=0)
            assert code.N == N, (code.N, N)
            rng = np.random.default_rng(N)
            vals = jnp.asarray(rng.standard_normal((N, V)), jnp.float32)
            erased = jnp.asarray(rng.random(N) < q)
            rx = jnp.where(erased[:, None], 0.0, vals)
            ts, outs = {}, {}
            for mode in ("dense_tile", "gather"):
                fn = jax.jit(lambda v, e, m=mode: tuple(peel_decode(
                    code, v, e, D, backend="pallas_seeded", bp=bp, bv=8,
                    seeded_mode=m)[:2]))
                ts[mode] = _median_seconds(lambda v, e: fn(v, e), rx,
                                           erased, reps=reps)
                outs[mode] = tuple(np.asarray(x) for x in fn(rx, erased))
            # tripwire: the TRAJECTORY is bit-exact across modes, and
            # never-erased coordinates pass through untouched
            still = ~np.asarray(erased)
            if (outs["gather"][1] != outs["dense_tile"][1]).any() or \
                    (outs["gather"][0][still]
                     != outs["dense_tile"][0][still]).any():
                raise AssertionError(
                    f"seeded_gather N={N}: gather round diverged from "
                    "dense_tile (erasure trajectory or known values)")
            rec.update({
                "timed": True,
                "median_s_dense_tile": ts["dense_tile"],
                "median_s_gather": ts["gather"],
                "wallclock_ratio_vs_dense_tile":
                    ts["gather"] / ts["dense_tile"],
                "interpret_mode": not on_tpu,
            })
        records.append(rec)
        rows.append([N, f"{dense_f / 1e6:.1f}", f"{gather_f / 1e6:.2f}",
                     f"{rec['flops_ratio_vs_dense_tile']:.0f}x",
                     (f"{rec['wallclock_ratio_vs_dense_tile']:.2f}x"
                      if timed else "-"),
                     "interp" if timed and not on_tpu else ""])
    return rows, records


def run_replay_sweep(*, N=8192, n_patterns=8, n_queries=64, q=0.25,
                     budget=32, reps=3, seed=0):
    """Pattern-compiled replay vs flooding sparse on a recurring stream.

    ``n_queries`` coded queries cycle through ``n_patterns`` distinct
    erasure patterns (straggler patterns are sticky in practice — that is
    the schedule cache's premise), so a cold :class:`ScheduleCache` over
    the stream realizes a hit rate of ``1 - n_patterns / n_queries``
    (0.875 at the defaults).  Three quantities per config:

    * modeled work — flooding runs every check row's ``r_max`` edges every
      round until fixpoint (+1 probe round); replay runs each resolving
      row's edges exactly once.  ``modeled_work_ratio`` is their quotient.
    * timed — per-query decode over the whole stream, flooding sparse
      adaptive vs warm-cache schedule replay (both jitted, same queries,
      same machine): ``cache_hit_speedup_vs_sparse`` is the same-run ratio
      ``check_regression.py --sections replay`` gates (hard ≥2× floor at
      N=8192).
    * tripwire — per pattern, the replay's values AND erasure trajectory
      must be bit-identical to the flooding sparse decode's (the "hi"
      tie-break rule exists for exactly this).

    The hit rate is read back from the obs ``sched_cache.hit_rate`` gauge
    (a scoped registry around the cold pass), so the gate also covers the
    cache's instrumentation path.  Returns (table_rows, json_records).
    """
    from repro.core import compile_peel_schedule
    from repro.core.schedule_cache import ScheduleCache
    from repro.obs import metrics as obs_metrics

    code = make_parity_only_ldpc(N // 2, l=3, r=6, seed=seed)
    assert code.N == N, (code.N, N)
    p = code.p
    r_max = code.check_idx.shape[1]
    rng = np.random.default_rng(seed)
    pats = rng.random((n_patterns, N)) < q                   # (P, N)
    # Any payload traces the same schedule (parity-only code: the decode
    # trajectory depends only on H and the mask, same as the large-N sweep).
    vals = rng.standard_normal((n_queries, N)).astype(np.float32)
    erased_np = pats[np.arange(n_queries) % n_patterns]      # (Q, N)
    rx_np = np.where(erased_np, 0.0, vals)
    rx = jnp.asarray(rx_np)
    er = jnp.asarray(erased_np)

    # modeled work: edge-ops per decode, averaged over the pattern set
    scheds = [compile_peel_schedule(code, pats[i]) for i in range(n_patterns)]
    flood_edges = float(np.mean(
        [(s.n_rounds + (0 if s.fully_resolved else 1)) * p * r_max
         for s in scheds]))
    replay_edges = float(np.mean(
        [max(s.n_resolved, 1) * r_max for s in scheds]))

    # bit-identity tripwire: replay ("hi" rule) vs single-pattern sparse
    sparse_fn = jax.jit(lambda v, e: tuple(peel_decode_adaptive(
        code, v, e, budget, backend="sparse")[:3]))
    for i in range(n_patterns):
        sv, se, sd = (np.asarray(x) for x in sparse_fn(rx[i], er[i]))
        dec = peel_decode_adaptive(code, rx[i], er[i], budget,
                                   backend="replay", schedule=scheds[i])
        if (np.asarray(dec.values) != sv).any() \
                or (np.asarray(dec.erased) != se).any() \
                or int(dec.rounds_used) != int(sd):
            raise AssertionError(
                f"replay N={N} pattern {i}: replay diverged from the "
                "flooding sparse decode (values, erasure trajectory, or "
                "round count)")

    # realized hit rate: a COLD cache over the stream, read back from the
    # obs gauge the cache maintains
    with obs_metrics.recording() as reg:
        cache = ScheduleCache()
        for i in range(n_queries):
            cache.get(code, erased_np[i])
        hit_rate = reg.gauge("sched_cache.hit_rate").value

    # timed: per-query decode over the whole stream (the cache is warm now
    # — every lookup hits, which is the steady state the gate is about)
    def serve_sparse():
        for i in range(n_queries):
            sparse_fn(rx[i], er[i])[0].block_until_ready()

    def serve_replay():
        for i in range(n_queries):
            s = cache.get(code, erased_np[i])
            peel_decode_adaptive(code, rx[i], er[i], budget,
                                 backend="replay", schedule=s
                                 ).values.block_until_ready()

    results = {}
    for mode, serve in (("sparse", serve_sparse), ("replay", serve_replay)):
        serve()  # compile + warm (one executable per distinct segment shape)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            serve()
            ts.append(time.perf_counter() - t0)
        results[mode] = float(np.median(ts))

    speedup = results["sparse"] / results["replay"]
    rec = {
        "N": N, "K": N // 2, "p": p, "r_max": r_max,
        "n_patterns": n_patterns, "n_queries": n_queries,
        "budget": budget, "erasure_q": q,
        "mean_flood_rounds": float(np.mean(
            [s.n_rounds + (0 if s.fully_resolved else 1) for s in scheds])),
        "mean_resolved": float(np.mean([s.n_resolved for s in scheds])),
        "modeled_flooding_edge_ops": flood_edges,
        "modeled_replay_edge_ops": replay_edges,
        "modeled_work_ratio": flood_edges / replay_edges,
        "median_s_sparse": results["sparse"],
        "median_s_replay": results["replay"],
        "per_query_us_sparse": results["sparse"] / n_queries * 1e6,
        "per_query_us_replay": results["replay"] / n_queries * 1e6,
        "cache_hit_speedup_vs_sparse": speedup,
        "schedule_cache_hit_rate": float(hit_rate),
        "cache_stats": cache.stats(),
        "bit_identical": True,      # the tripwire above raises otherwise
        "jax_backend": jax.default_backend(),
    }
    rows = [[N, n_patterns, n_queries,
             f"{rec['modeled_work_ratio']:.0f}x",
             f"{rec['per_query_us_sparse']:.0f}",
             f"{rec['per_query_us_replay']:.0f}",
             f"{speedup:.2f}x", f"{hit_rate:.3f}"]]
    return rows, [rec]


def run(*, Ks=(64, 256, 1024), ss=(2, 8, 24), reps=10):
    rows = []
    for K in Ks:
        code = make_regular_ldpc(K, l=3, r=6, seed=0)
        G = jnp.asarray(code.G, jnp.float32)
        rng = np.random.default_rng(0)
        cw = jnp.asarray(code.encode(rng.standard_normal(K)), jnp.float32)
        for s in ss:
            key = jax.random.PRNGKey(s)
            mask = FixedCountStragglers(s).sample(key, code.N)
            rx = jnp.where(mask, 0.0, cw)

            dec = peel_decode_adaptive(code, rx, mask)
            rounds = int(dec.rounds_used)
            unresolved = int(dec.erased.sum())

            f = jax.jit(lambda v, e: peel_decode_adaptive(code, v, e).values)
            f(rx, mask).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(reps):
                f(rx, mask).block_until_ready()
            t_ldpc = (time.perf_counter() - t0) / reps

            # MDS-style exact recovery: weighted lstsq on surviving rows
            def mds(v, e):
                alive = (~e).astype(jnp.float32)
                sol, *_ = jnp.linalg.lstsq(G * alive[:, None], v * alive)
                return sol

            g = jax.jit(mds)
            g(rx, mask).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(reps):
                g(rx, mask).block_until_ready()
            t_lstsq = (time.perf_counter() - t0) / reps

            rows.append([code.N, K, s, rounds, unresolved,
                         f"{t_ldpc*1e6:.0f}", f"{t_lstsq*1e6:.0f}",
                         f"{t_lstsq/max(t_ldpc,1e-12):.1f}x"])
    return rows


def main(quick: bool = False, json_path: str | Path = BENCH_JSON,
         backend: str | None = None, obs_out: str | Path | None = None):
    from repro.obs import ObsSession
    session = ObsSession.start(obs_out)
    try:
        return _main(quick=quick, json_path=json_path, backend=backend)
    finally:
        session.finish()


def _main(quick: bool = False, json_path: str | Path = BENCH_JSON,
          backend: str | None = None):
    if backend:
        # Forced-backend run (the VMEM-failover bugfix path): resolve the
        # request with a clear message and run ONE size past the limit
        # (N=2048 triggers both failovers: > interpret budget off-TPU,
        # > VMEM budget on TPU) — proving the path, not re-measuring the
        # sweep.  Leaves the committed JSON alone.
        lrows, _ = run_large_n_sweep(Ns=(2048,), reps=1,
                                     forced_backend=backend)
        print_table(f"Large-N sweep — forced backend {backend!r} "
                    "(failover-resolved)",
                    ["N", "K", "backend", "decode_us", "round_us",
                     "speedup_vs_dense", ""], lrows)
        return lrows

    # 1. backend scaling (the per-PR perf trajectory)
    Ks = (64, 256, 1024) if quick else (64, 256, 512, 1024, 2048)
    brows, records = run_backend_scaling(Ks=Ks, reps=3 if quick else 5)
    print_table("Decode backends — fixed-D latency (dense vs sparse vs "
                "fused-Pallas)",
                ["N", "K", "backend", "decode_us", "round_us",
                 "achieved_GFLOP/s", "speedup"], brows)

    # 2. batched decode over independent erasure patterns (serving axis)
    # K=64 (N=128) exists so the batched-Pallas kernel is exercised off-TPU
    # too (interpret mode, small N only — see _PALLAS_CPU_MAX_N).
    batch_rows, batch_records = run_batched_scaling(
        Ks=(1024,) if quick else (64, 256, 1024),
        Bs=(1, 64) if quick else (1, 8, 64, 256),
        reps=3 if quick else 5)
    print_table("Batched decode — B independent erasure patterns, one launch",
                ["N", "K", "B", "mode", "per_query_us", "speedup_vs_seq"],
                batch_rows)

    # 2b. mixed light/heavy serving: continuous admission vs lockstep waves
    # (B=64, N=2048 — the acceptance config).  Quick mode trims only reps:
    # the sweep config must stay IDENTICAL to the committed baseline's so
    # check_regression finds matching records to gate.
    serve_rows, serve_records = run_serving_sweep(reps=2 if quick else 3)
    print_table("Serving sweep — mixed light/heavy stragglers, mean "
                "per-query decode cost",
                ["N", "B", "mode", "launches", "launch_rounds",
                 "per_query_us", "speedup_vs_lockstep"], serve_rows)

    # 6. large-N sweep — the check-axis-tiled regime.  The config is FIXED
    # (identical in quick mode, reps included: the whole sweep is ~20 s and
    # the gated dense/sparse ratio is noise-sensitive at reps=2) so
    # check_regression always finds matching (backend, N, D) records.
    lrows, large_records = run_large_n_sweep(reps=3)
    print_table("Large-N sweep — past the whole-H-in-VMEM regime "
                "(tiled kernel where timeable)",
                ["N", "K", "backend", "decode_us", "round_us",
                 "speedup_vs_dense", ""], lrows)

    # 7. seeded sweep — in-kernel H regeneration vs streamed H.  Config is
    # FIXED in quick mode (the sweep is modeled arithmetic + one timed N +
    # one lower-only record, ~seconds total) so check_regression always
    # finds matching (N, D) records.
    srows, seeded_records = run_seeded_sweep(reps=3)
    print_table("Seeded sweep — modeled operand HBM traffic and wall-clock, "
                "seeded vs check-axis-tiled",
                ["N", "tiled_MiB", "seeded_MiB", "traffic_ratio",
                 "wallclock_ratio", ""], srows)

    # 8. seeded-gather sweep — edge-proportional rounds vs dense tiles.
    # Fixed config in quick mode for the same reason as section 7 (modeled
    # arithmetic + one timed N, seconds total; the gate needs matching
    # (N, D, V) records).
    sgrows, seeded_gather_records = run_seeded_gather_sweep(reps=3)
    print_table("Seeded-gather sweep — modeled per-round FLOPs and "
                "wall-clock, gather vs dense-tile rounds",
                ["N", "dense_MFLOP", "gather_MFLOP", "flops_ratio",
                 "wallclock_ratio", ""], sgrows)

    # 9. replay sweep — pattern-compiled peeling on a recurring stream.
    # Config is FIXED in quick mode (reps trimmed only): the gate needs a
    # matching (N, n_queries, n_patterns, budget) record, and the hard
    # speedup floor is a same-run ratio either way.
    rrows, replay_records = run_replay_sweep(reps=2 if quick else 3)
    print_table("Replay sweep — cache-hit schedule replay vs flooding "
                "sparse, recurring straggler stream",
                ["N", "P", "Q", "work_ratio", "sparse_us", "replay_us",
                 "speedup", "hit_rate"], rrows)

    # 3+5. adaptivity & vs-lstsq
    rows = run(Ks=(64, 256) if quick else (64, 256, 1024))
    print_table("Decoder scaling — adaptive peeling vs least-squares recovery",
                ["N", "K", "s", "rounds", "unresolved",
                 "ldpc_us", "lstsq_us", "speedup"], rows)

    # 4. D-monotonicity (Remark 3)
    code = make_regular_ldpc(256, l=3, r=6, seed=1)
    rng = np.random.default_rng(1)
    erased = jnp.asarray(rng.random(code.N) < 0.25)
    dummy = jnp.zeros((code.N,), jnp.float32)
    drows = [[D, int(peel_decode(code, dummy, erased, D).erased.sum())]
             for D in (0, 1, 2, 4, 8, 16)]
    print_table("Unresolved coordinates vs decode rounds D (q0≈0.25)",
                ["D", "unresolved"], drows)

    out = {
        "benchmark": "decoder_scaling",
        # v6: adds the "seeded" section (in-kernel H regeneration: modeled
        # operand-traffic ratio vs the tiled kernel, gated ≥10× at N=16384,
        # plus the timed + lower-only feasibility records).
        # v8: adds the "seeded_gather" section (edge-proportional gather
        # rounds: modeled per-round FLOPs ratio vs the dense regenerated
        # tile — the hwcaps crossover model — gated ≥8× at N=16384, plus a
        # timed interpret record with a trajectory tripwire).
        # v10: adds the "replay" section (pattern-compiled peeling: modeled
        # flooding/replay work ratio, the timed cache-hit replay speedup on
        # a recurring straggler stream — gated ≥2× at N=8192 — the realized
        # schedule-cache hit rate via the obs gauge, and the bit-identity
        # tripwire).
        "schema_version": 10,
        "jax_backend": jax.default_backend(),
        "fused_decode_single_kernel_launch": True,  # see ldpc_peel/ops.py
        "backend_scaling": records,
        "batched_scaling": batch_records,
        "serving_sweep": serve_records,
        "large_n": large_records,
        "seeded": seeded_records,
        "seeded_gather": seeded_gather_records,
        "replay": replay_records,
        "adaptive_vs_lstsq": [
            dict(zip(["N", "K", "s", "rounds", "unresolved",
                      "ldpc_us", "lstsq_us", "speedup"], r)) for r in rows
        ],
        "d_monotonicity": [dict(zip(["D", "unresolved"], r)) for r in drows],
    }
    # since schema v4: the distributed sweep (distributed_scaling.py, run
    # on its own fake-worker mesh process) appends its section to the same
    # file — carry it through instead of dropping it on rewrite.
    try:
        prev = json.loads(Path(json_path).read_text())
        if "distributed_scaling" in prev:
            out["distributed_scaling"] = prev["distributed_scaling"]
    except (FileNotFoundError, json.JSONDecodeError):
        pass
    Path(json_path).write_text(json.dumps(out, indent=2))
    print(f"\nwrote {json_path}")
    return brows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--backend", default=None,
                    choices=["dense", "sparse", "pallas", "pallas_tiled",
                             "pallas_seeded"],
                    help="FORCE one decode backend through the large-N "
                         "sweep (failover-resolved past the VMEM limit — "
                         "or past a missing seed — instead of crashing); "
                         "skips the JSON rewrite")
    ap.add_argument("--obs-out", default=None, metavar="PATH",
                    help="export obs metrics JSONL (+ .trace.json spans) "
                         "from the instrumented sweeps to PATH")
    a = ap.parse_args()
    main(quick=a.quick, backend=a.backend, obs_out=a.obs_out)
