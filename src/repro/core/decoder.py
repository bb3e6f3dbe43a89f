"""Iterative (peeling) erasure decoder for real-valued LDPC codes, in JAX.

The classic peeling decoder resolves degree-1 checks one at a time.  On TPU
we use the equivalent *flooding* schedule: in each round, every parity check
with exactly one erased neighbour resolves that neighbour.  The fixed number
of rounds ``D`` is exactly the paper's decoding-iteration knob — the quality
of the recovered gradient is monotone in ``D`` (Remark 3).

Backend matrix (``backend=`` on :func:`peel_decode` /
:func:`peel_decode_adaptive` / :func:`peel_decode_batch` /
:func:`peel_decode_batch_adaptive`):

=========  ==================================================================
backend    what runs
=========  ==================================================================
"dense"    the original reference: three dense ``H``-structured ops per
           round (mask matvec, matmul, argmax) — O(p·N·V) work.  Always
           available, including for raw ``(H, Hb)`` tuples.  Batched decode
           vmaps the whole fixed-D loop over the pattern axis; batched
           ADAPTIVE decode vmaps the early-exit while_loop (per-slot
           predicates — a converged slot's carry freezes while stragglers
           keep peeling).
"sparse"   gathers over the code's padded neighbor table
           (``LDPCCode.check_idx`` / ``check_coeff``) — O(p·r_max·V) work,
           i.e. proportional to the Tanner-graph edge count, the complexity
           the paper's low-cost-decoding argument assumes.  Requires an
           :class:`LDPCCode` (the table is built at construction).  Batched
           decode vmaps the loop with the neighbor table broadcast (loaded
           once, shared across all B patterns).  Batched ADAPTIVE decode
           keeps the scatter-free batch-major round and threads a per-slot
           ACTIVE mask through a single while_loop: converged slots'
           columns are frozen (select, no gather feedback) and the loop
           exits when every slot has converged or exhausted its budget.
"pallas"   the fused one-kernel decodes (:mod:`repro.kernels.ldpc_peel`):
           the whole decode runs inside a single ``pallas_call`` with ``H``
           resident in VMEM — no per-round kernel relaunch or re-padding.
           Fixed-D (``peel_decode``), early-exit adaptive
           (``peel_decode_adaptive``: in-kernel while_loop on the
           unresolved count), batched (``peel_decode_batch``: grid over
           the B independent erasure patterns with the H tile shared across
           the batch), and batched-adaptive
           (``peel_decode_batch_adaptive``: grid over slots, one in-kernel
           while_loop PER SLOT with a traced per-slot round budget) are
           each ONE launch.  Runs in interpret mode off-TPU (correct but
           not fast on CPU).

           Layout (:func:`decode_layout`, counted as
           ``decoder.layout_total{layout}``): the kernels carry the
           payload LANE-MAJOR, ``(V, N)`` with the code on lanes, which
           suits a narrow payload.  A fixed-D decode of a wide payload
           (``V >= 512`` lanes, e.g. a gradient of width dim under the
           (40, 20) code) runs SYMBOL-MAJOR
           instead: the payload stays ``(N, V)`` as the workers produce
           it, the erasure trajectory is solved once per call on H and the
           mask, and one pass over lane tiles (:func:`pick_tile_lanes`)
           copies the known rows and computes only the resolved ones
           (:func:`repro.kernels.ldpc_peel.peel_decode_symbol_major_pallas`).
           Same trajectory; erased coordinates left unresolved come back
           as 0.  The crossover, near 340 lanes for N = 40 and N = 896,
           was measured on one TPU v5e (PERF.md, the V sweep).
"pallas_tiled"
           the same four one-launch contracts with ``H`` STREAMED over
           CHECK tiles from HBM (``bp`` rows at a time, double-buffered
           DMA) while the value carry lives in VMEM — problem size is
           bounded by HBM, not whole-H-in-VMEM, so the fused decode serves
           N ∈ {4096, 8192, 16384, ...}.  Identical erasure trajectories
           (every tile's proposal is computed against the round-start
           state; ascending tiles keep the lowest-index-check tie-break);
           values match "pallas" up to f32 summation order (XLA may block
           a tile's row-sum reduction differently than the whole-H one).
           Tile knobs: ``bp`` (check-tile height; default sized from the
           VMEM budget via :func:`pick_tile_bp`) and ``bv`` (payload tile).

           Layout (:func:`decode_layout`): on TPU the fixed-D
           single-pattern decode of a narrow payload (``V <=
           _EDGE_PERMUTED_MAX_V``) runs EDGE-PERMUTED instead, and reads
           no H: the code's edges are laid out twice, variable-major and
           check-major, as slot-major planes of whole vregs, and a Beneš
           network routed once per code on the host
           (:attr:`LDPCCode.edge_network`, :mod:`repro.core.benes`)
           permutes between the two.  Each round broadcasts values and
           erasure flags onto the variable-major edges, permutes them,
           counts and sums each check over its planes, proposes on the
           erased edge of each solvable check, permutes back and keeps
           each variable's lowest resolving check: the same trajectory,
           in one launch with the carry in VMEM, ending early at the
           first round that resolves nothing (the state is then final)
           (:func:`repro.kernels.ldpc_peel.peel_decode_edge_permuted_pallas`).
           Taken where the network's working set fits the VMEM budget;
           the batched and adaptive entry points keep the streamed kernel.
           Its cost grows with V, one payload column a grid step; the
           streamed kernel's does not, and the two cross near V = 11 at
           N = 16384 on one TPU v5e (PERF.md, PR 16).
"pallas_seeded"
           the same four one-launch contracts with NO ``H`` operand at all:
           each ``bp×N`` check tile is REGENERATED in-register from the
           code's counter-based seed inside the flooding round
           (:func:`repro.kernels.ldpc_peel.seeded_h_tile`).  Requires a
           seeded parity-only code — ``make_seeded_ldpc`` (materialized,
           ``kind="ldpc-seeded"``) or the structure-only
           :class:`repro.core.ldpc.SeededLDPC`, which never builds H at
           any size.  Erasure trajectories are bit-identical to every
           other backend on the same code and VALUES are bit-identical to
           "pallas_tiled" (same tile-shaped summation); H costs zero bytes
           of HBM storage and operand traffic.

           ``seeded_mode`` sub-dispatches the ROUND implementation:

           * "dense_tile" (default) — regenerate the full ``bp×N`` tile and
             run the tiled round's dense contractions on it (MXU-friendly,
             but O(p·N) FLOPs per round even though only r of N entries
             per check row are nonzero);
           * "gather" — generate only the r (column, weight) pairs per
             check row from the seed and run the check pass as gather +
             segment-sum, merging resolutions through the layered
             permutation's INVERSE map (first-tile-wins, lowest-check
             tie-break preserved) — O(p·r) FLOPs per round, the
             edge-proportional cost the paper's low-overhead-decoding
             claim assumes.  Erasure trajectories (masks AND round counts)
             are bit-identical to "dense_tile"; decoded values agree up to
             f32 summation order.
           * "auto" — crossover rule from :mod:`repro.core.hwcaps`:
             "gather" iff the dense round's modeled FLOPs exceed
             ``mxu_advantage ×`` the gather round's (advantage 1.0 on
             CPU/interpret — gather always wins); on TPU always
             "dense_tile", because the gather round does not compile
             there (Mosaic rejects its in-kernel gathers: a compiled
             "gather" launch raises ``NotImplementedError``).
"replay"   straight-line numeric REPLAY of a pattern-compiled
           :class:`PeelSchedule` — no round loop, no convergence test, no
           solvability counting: the elimination order is a pure function
           of ``(code, erasure pattern)``, so :func:`compile_peel_schedule`
           solves it ONCE symbolically (host-side numpy) and the replay
           executors run only the resolving checks' gather/FMA arithmetic,
           O(resolved edges) total.  Pass the schedule explicitly
           (``schedule=`` / per-slot ``schedules=``, e.g. from a
           :class:`repro.core.schedule_cache.ScheduleCache` hit — required
           under jit, where the mask is a tracer) or let a concrete mask
           solve on the fly.  Values are BIT-IDENTICAL to the flooding
           backends: single-pattern replay applies the "hi" duplicate-check
           tie-break (matching dense/sparse last-write-wins scatters),
           batched replay the "lo" rule (matching the batch-major scan and
           the Pallas kernels); adaptive round counts reproduce the
           while_loop's stopping rule, probe round included.  On TPU the
           batched replay can also run as ONE fused ``pallas_call``
           (:func:`repro.kernels.ldpc_peel.peel_decode_replay_pallas`).
           Requires an :class:`LDPCCode`.
"auto"     "dense" for raw tuples and small codes (N < 256); "sparse" for
           large codes off-TPU; on TPU, "pallas_seeded" whenever the code
           carries a regenerable seed, else "pallas" when
           :func:`vmem_bytes_estimate` says the resident kernel's
           per-grid-step working set fits the VMEM budget
           (``vmem_budget_bytes``, default 8 MiB of the ~16 MiB/core), and
           "pallas_tiled" otherwise.  A structure-only
           :class:`~repro.core.ldpc.SeededLDPC` resolves to
           "pallas_seeded" on EVERY platform (it is the only backend that
           can run without H; off-TPU it runs in interpret mode).  The
           same rule applies on the batch axis (the batched kernel's
           per-step working set matches the single-pattern kernel's), and
           to the batched-adaptive decode.
=========  ==================================================================

Memory cost per backend (H-side, f32): "dense"/"sparse"/"pallas" hold the
materialized ``(p, N)`` H (or its neighbor table) resident — HBM storage
AND per-round operand traffic scale as ``p·N``; "pallas_tiled" still
STORES ``p·N`` in HBM but holds only ``2·bp·N`` in VMEM, streaming the
rest; "pallas_seeded" stores a few ints (the seed/spec) and moves ZERO H
bytes — storage and traffic are both O(1) in the code size.

All backends follow bit-identical erasure trajectories (solvability is an
exact count of erased neighbours, and every backend resolves the same
first-erased-column neighbour per check); decoded values agree up to f32
summation order.  The batched entry point decodes each pattern exactly as
the single-pattern entry point would — ``decode_batch`` of B patterns and a
Python loop of B ``decode`` calls land on the same trajectories.

The decoder is fully ``jit``-able (fixed ``D`` → ``lax.fori_loop``;
adaptive → ``lax.while_loop`` with early exit) and batched over symbol
payloads: ``values`` may be ``(N,)`` scalars (the paper's inner products) or
``(N, V)`` vectors (coded gradient aggregation, where each symbol is a chunk
of a partial gradient).  :func:`peel_decode_batch` adds the second,
orthogonal batch axis — B *independent erasure patterns* decoded in one
launch, the serving-side concurrency axis (many coded queries, each with its
own straggler realization).

Erased coordinates that remain unresolved are left as-is in ``values`` but
flagged in the returned mask; callers zero-fill per the paper's Scheme 2
(both ``ĉ`` and ``b̂`` are zeroed on the unresolved set so the estimate stays
an unbiased scaled gradient — Lemma 1).  The encode→erase→decode→epilogue
composition lives one layer up in :mod:`repro.core.engine`.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.lax import Precision
import numpy as np

from repro.core.benes import edge_geometry
from repro.core.ldpc import (
    LDPCCode,
    SeededLDPC,
    SeededStructure,
    seeded_structure_of,
)
from repro.obs import metrics as _obs_metrics

__all__ = [
    "DecodeResult",
    "PeelSchedule",
    "compile_peel_schedule",
    "erasure_mask_key",
    "peel_round",
    "peel_round_sparse",
    "peel_round_sparse_batch",
    "peel_fixed_dense",
    "peel_fixed_sparse",
    "peel_decode",
    "peel_decode_adaptive",
    "peel_decode_batch",
    "peel_decode_batch_adaptive",
    "erased_after",
    "resolve_backend",
    "vmem_bytes_estimate",
    "pick_tile_bp",
    "peel_error_bound",
    "F32_OP_ERROR",
    "SEEDED_MODES",
]

BACKENDS = ("auto", "dense", "sparse", "pallas", "pallas_tiled",
            "pallas_seeded", "replay")
# Sub-dispatch of "pallas_seeded": how each flooding round is computed.
SEEDED_MODES = ("auto", "dense_tile", "gather")

# "auto" picks the sparse neighbor-table round once the dense round's O(p·N)
# work clearly loses to O(p·r_max) gathers; below this the dense matmul's
# better vectorization wins on CPU.
_AUTO_SPARSE_MIN_N = 256
# VMEM budget the "auto" dispatch sizes the fused kernels against: half of
# the ~16 MiB/core, leaving headroom for the pipeline's own double
# buffering.  Overridable per call/engine via ``vmem_budget_bytes``.
_DEFAULT_VMEM_BUDGET_BYTES = 8 * 2**20
# Narrowest payload the "pallas" fixed-D decode carries symbol-major
# (decode_layout); below it the lane-major resident kernel stays.  On one
# TPU v5e the two cross near 340 lanes for N = 40 and for N = 896 alike
# (PERF.md, the V sweep): symbol-major's trajectory solve is a fixed cost.
_SYMBOL_MAJOR_MIN_V = 512
# Widest payload the "pallas_tiled" fixed-D decode carries on the code's
# edges (decode_layout); past it the streamed-H kernel stays.  On one TPU
# v5e at N = 16384 the edge kernel took 1.41 ms a payload column against
# 16.12 ms for the streamed decode of any width it fits: they cross near
# V = 11 (PERF.md, PR 16's three-way timing).
_EDGE_PERMUTED_MAX_V = 8


def _kernel_shape(code) -> tuple[int, int]:
    """(p, N) of an LDPCCode / SeededLDPC, an (H, Hb) tuple, or a raw
    (p, N) int pair."""
    if isinstance(code, (LDPCCode, SeededLDPC)):
        return code.p, code.N
    a, b = code
    if isinstance(a, (int, np.integer)):
        return int(a), int(b)
    return a.shape[0], a.shape[1]


def vmem_bytes_estimate(code, dtype=jnp.float32, batch: int = 1, *,
                        bv: int = 8) -> int:
    """Estimated per-grid-step VMEM working set of the RESIDENT fused kernel.

    ``code`` may be an :class:`LDPCCode`, an ``(H, Hb)`` tuple, or a raw
    ``(p, N)`` shape pair.  The resident kernel keeps several ``(p, N)``
    buffers live per round (H itself plus its boolean mask, the column/row
    iotas, and the resolution one-hot) alongside the lane-major ``(bv, N)``
    payload carry (``bv`` payload rows per grid step) and the ``(1, N)``
    masks; the estimate counts them at the kernel's f32 compute width
    (``dtype`` below f32 still computes in f32).  The batch axis shares H
    and streams one slot's payload per grid step, so ``batch`` does not
    scale the per-step set — the argument is accepted (and validated) so
    call sites can pass their batch size symmetrically.

    ``backend="auto"`` compares this against ``vmem_budget_bytes`` to pick
    resident-"pallas" vs "pallas_tiled"; benchmarks use it to fail over
    with a clear message instead of crashing past the VMEM limit.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1; got {batch}")
    p, N = _kernel_shape(code)
    esize = max(jnp.dtype(dtype).itemsize, 4)
    Npad = N + (-N) % 128
    ppad = p + (-p) % 8
    h_like = 5 * ppad * Npad * esize        # H, Hb, col/row iota, one-hot
    payload = 3 * Npad * bv * esize         # carry + known + scattered
    masks = 3 * Npad * esize                # erasure mask + resolved flags
    return h_like + payload + masks


def pick_tile_bp(code, *, vmem_budget_bytes: int | None = None) -> int:
    """Check-tile height for the tiled kernels: the tallest 8-aligned tile
    whose double-buffered ``(2, bp, N)`` stream stays within ~half of the
    VMEM budget (the other half holds the value carry and round
    temporaries).  Clamped to [8, p]."""
    budget = vmem_budget_bytes or _DEFAULT_VMEM_BUDGET_BYTES
    p, N = _kernel_shape(code)
    Npad = N + (-N) % 128
    bp = (budget // 2) // (2 * Npad * 4)
    bp -= bp % 8
    return int(max(8, min(bp, p + (-p) % 8)))


def decode_layout(backend: str, V: int, code=None, *,
                  vmem_budget_bytes: int | None = None) -> str:
    """How a resolved fixed-D single-pattern decode lays out its payload:
    "symbol_major" where ``backend == "pallas"`` and the ``(N, V)`` payload
    is at least ``_SYMBOL_MAJOR_MIN_V`` lanes wide; "edge_permuted" on TPU
    where ``backend == "pallas_tiled"``, ``code`` is an :class:`LDPCCode`
    whose edge network fits the VMEM budget and the payload is at most
    ``_EDGE_PERMUTED_MAX_V`` wide; else "lane_major" (every other Pallas
    kernel, and the XLA backends, which take the payload as given).  The
    symbol-major crossover was the same for N = 40 and N = 896."""
    if backend == "pallas" and V >= _SYMBOL_MAJOR_MIN_V:
        return "symbol_major"
    if (backend == "pallas_tiled" and V <= _EDGE_PERMUTED_MAX_V
            and isinstance(code, LDPCCode)
            and jax.default_backend() == "tpu"
            and edge_geometry(code.check_idx, code.N).vmem_bytes
            <= (vmem_budget_bytes or _DEFAULT_VMEM_BUDGET_BYTES)):
        return "edge_permuted"
    return "lane_major"


def pick_tile_lanes(code, V: int, *,
                    vmem_budget_bytes: int | None = None) -> int:
    """Lane-tile width of the symbol-major decode: the widest multiple of
    512 lanes (at least 512) whose double-buffered ``(N, bv)`` payload and
    output blocks fit the VMEM budget, and no wider than ``V`` rounded up
    to 128 lanes."""
    budget = vmem_budget_bytes or _DEFAULT_VMEM_BUDGET_BYTES
    _, N = _kernel_shape(code)
    bv = budget // (4 * (N + (-N) % 8) * 4)
    bv = max(512, bv - bv % 512)
    return int(min(bv, V + (-V) % 128))


class DecodeResult(NamedTuple):
    values: jax.Array  # (N,) / (N, V); batched: (B, N) / (B, N, V)
    erased: jax.Array  # (N,) bool (batched: (B, N)); True where unresolved
    # () int32 (== D for fixed-D decode); the batched-adaptive decode
    # returns the PER-SLOT vector (B,) int32 — each slot's own round count.
    rounds_used: jax.Array


def _expand(values: jax.Array) -> tuple[jax.Array, bool]:
    if values.ndim == 1:
        return values[:, None], True
    return values, False


def resolve_backend(backend: str, code, *, adaptive: bool = False,
                    vmem_budget_bytes: int | None = None) -> str:
    """Resolve the ``backend=`` knob to a concrete decode implementation.

    See the module docstring for the matrix.  Raises on unknown names and on
    sparse/pallas requests for raw ``(H, Hb)`` tuples (no neighbor table).
    Since the adaptive decode gained its own fused kernel (in-kernel
    while_loop), ``adaptive`` no longer downgrades "pallas".  On TPU,
    ``"auto"`` dispatches on :func:`vmem_bytes_estimate` against
    ``vmem_budget_bytes`` (not a hardcoded N threshold): resident "pallas"
    while the whole working set fits, "pallas_tiled" beyond it.
    """
    del adaptive  # kept for call-site compatibility; all modes have kernels
    if backend not in BACKENDS:
        raise ValueError(f"unknown decode backend {backend!r}; want one of {BACKENDS}")
    requested = backend
    is_code = isinstance(code, LDPCCode)
    seeded_h = isinstance(code, SeededLDPC) or (
        is_code and code.kind == "ldpc-seeded")
    if backend == "auto":
        if isinstance(code, SeededLDPC):
            # Structure-only: no H exists at any size — the seeded kernel
            # is the only backend that can run it (interpret off-TPU).
            backend = "pallas_seeded"
        elif not is_code:
            backend = "dense"
        elif jax.default_backend() == "tpu":
            if seeded_h:
                backend = "pallas_seeded"
            else:
                budget = vmem_budget_bytes or _DEFAULT_VMEM_BUDGET_BYTES
                backend = ("pallas" if vmem_bytes_estimate(code) <= budget
                           else "pallas_tiled")
        else:
            backend = "sparse" if code.N >= _AUTO_SPARSE_MIN_N else "dense"
    if backend == "pallas_seeded" and not seeded_h:
        kind = code.kind if is_code else type(code).__name__
        raise ValueError(
            "backend='pallas_seeded' needs a seeded parity-only code "
            "(make_seeded_ldpc / SeededLDPC) whose H is regenerable from "
            f"its seed; got {kind!r}")
    if isinstance(code, SeededLDPC) and backend != "pallas_seeded":
        raise ValueError(
            f"backend={backend!r} needs a materialized H, but a SeededLDPC "
            "is structure-only; use backend='pallas_seeded'/'auto' or build "
            "the code with make_seeded_ldpc")
    if backend in ("sparse", "pallas", "pallas_tiled", "replay") and not is_code:
        raise ValueError(
            f"backend={backend!r} needs an LDPCCode (neighbor table); "
            "raw (H, Hb) tuples only support backend='dense'"
        )
    reg = _obs_metrics.active()
    if reg is not None:
        # One increment per RESOLUTION (construction/trace), not per decode:
        # jit-cache hits re-run nothing, so counts track dispatch decisions.
        reg.counter("decoder.resolve_total",
                    requested=requested, resolved=backend).inc()
    return backend


def _count_layout(backend: str, V: int, code,
                  vmem_budget_bytes: int | None) -> str:
    """:func:`decode_layout`, counted as ``decoder.layout_total{layout}``
    in the active registry: one increment per trace of a fixed-D Pallas
    decode, like ``decoder.resolve_total``."""
    layout = decode_layout(backend, V, code,
                           vmem_budget_bytes=vmem_budget_bytes)
    reg = _obs_metrics.active()
    if reg is not None:
        reg.counter("decoder.layout_total", layout=layout).inc()
    return layout


# --------------------------------------------------------------- dense round


def peel_round(
    H: jax.Array, Hb: jax.Array, values: jax.Array, erased: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """One flooding round (dense). values: (N, V), erased: (N,) bool.

    For every check row with exactly one erased neighbour ``j``:
      ``c_j = -(sum_{j' known} H[i, j'] c_{j'}) / H[i, j]``.
    Rows that resolve the same coordinate write consistent values (they are
    parity checks of the same codeword), so duplicate scatters are benign.
    """
    N = values.shape[0]
    e = erased.astype(H.dtype)  # (N,)
    # (p,) number of erased neighbours per check
    cnt = jnp.matmul(Hb.astype(H.dtype), e, precision=Precision.HIGHEST)
    solvable = cnt == 1.0  # (p,)
    known = values * (1.0 - e)[:, None]  # zero out erased entries
    row_sums = jnp.matmul(H, known, precision=Precision.HIGHEST)  # (p, V)
    # The (unique) erased neighbour of each row; arbitrary for non-solvable rows.
    pos = jnp.argmax(Hb & erased[None, :], axis=1)  # (p,)
    coeff = jnp.take_along_axis(H, pos[:, None], axis=1)[:, 0]  # (p,)
    new_val = -row_sums / jnp.where(coeff == 0.0, 1.0, coeff)[:, None]
    # Out-of-bounds scatter with mode="drop" discards non-solvable rows.
    safe_pos = jnp.where(solvable, pos, N)
    values = values.at[safe_pos].set(new_val, mode="drop")
    erased = erased.at[safe_pos].set(False, mode="drop")
    return values, erased


@partial(jax.jit, static_argnames=("iters",))
def peel_fixed_dense(H, Hb, values, erased, iters: int):
    """``iters`` dense flooding rounds as one jitted loop.

    Operands are plain arrays (shardable / usable inside foreign jit
    contexts — this is what the sharded launch steps call); ``values``
    (N, V), ``erased`` (N,) bool.
    """
    def body(_, carry):
        v, e = carry
        return peel_round(H, Hb, v, e)

    values, erased = jax.lax.fori_loop(0, iters, body, (values, erased))
    return values, erased


# -------------------------------------------------------------- sparse round


def _edge_sum(nv: jax.Array, w: jax.Array) -> jax.Array:
    """Known-neighbor contribution sum over the r_max slot axis (axis 1).

    ``nv (rows, r_max, ...)`` gathered neighbor values, ``w (rows, r_max)``
    pre-masked edge weights (0 on erased/padding slots).  Evaluated as the
    canonical left-to-right multiply-add chain with the ADDS inside a
    ``lax.scan`` and the products outside it.  Two codegen hazards make a
    plain reduce/unrolled chain produce different last-ulp bits for the
    SAME row depending on how many rows the operands carry: XLA re-blocks
    reductions by shape, and LLVM contracts mul+add pairs into FMAs
    shape-dependently inside fused loops (``optimization_barrier`` is
    removed by the CPU pipeline before fusion, so it cannot pin either).
    Fusion never crosses a while-loop boundary, so the scan body holds
    only adds/subs/compares with no multiply to contract, and the
    products are lone muls — every output element is the same fixed IEEE
    op sequence at ANY row count.  This shape-stability is what lets
    ``backend="replay"`` recompute only the resolving checks' rows
    bit-identically to the full flooding rounds.  The body runs Neumaier
    compensated summation, so the sum is also ~1 ulp from exact — tighter
    than the reduce it replaces, keeping the cross-backend (dense/pallas)
    agreement tolerances comfortable.
    """
    wx = w.reshape(w.shape + (1,) * (nv.ndim - w.ndim))
    pt = jnp.moveaxis(nv * wx, 1, 0)                # (r_max, rows, ...)

    def body(carry, x):
        s, c = carry
        t = s + x
        big = jnp.abs(s) >= jnp.abs(x)
        c = c + jnp.where(big, (s - t) + x, (x - t) + s)
        return (t, c), None

    (s, c), _ = jax.lax.scan(body, (pt[0], jnp.zeros_like(pt[0])), pt[1:])
    return s + c


def peel_round_sparse(
    check_idx: jax.Array,
    check_coeff: jax.Array,
    values: jax.Array,
    erased: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """One flooding round via neighbor-table gathers — O(p·r_max·V) work.

    ``check_idx (p, r_max) int32`` holds each check's neighbour columns in
    ascending order, padded with the sentinel ``N``; ``check_coeff`` the
    matching edge weights, padded with 0.  Gathers read from ``values`` /
    ``erased`` padded by one sentinel row, so padding slots contribute
    nothing and no branching is needed.  Semantics match :func:`peel_round`
    exactly: same solvability decisions, same resolved neighbour per check.
    """
    N = values.shape[0]
    dt = values.dtype
    e_pad = jnp.concatenate([erased, jnp.zeros((1,), erased.dtype)])  # (N+1,)
    v_pad = jnp.concatenate([values, jnp.zeros((1, values.shape[1]), dt)])
    ne = e_pad[check_idx]  # (p, r_max) bool — erased neighbours
    nef = ne.astype(dt)
    cnt = nef.sum(axis=1)  # (p,)
    nv = v_pad[check_idx]  # (p, r_max, V)
    # Known-neighbour contribution: coeff * value, erased slots zeroed.
    sums = _edge_sum(nv, check_coeff.astype(dt) * (1.0 - nef))
    # First erased neighbour slot (ascending column order == dense argmax).
    slot = jnp.argmax(ne, axis=1)  # (p,)
    pos = jnp.take_along_axis(check_idx, slot[:, None], axis=1)[:, 0]
    coeff = jnp.take_along_axis(check_coeff, slot[:, None], axis=1)[:, 0].astype(dt)
    solvable = cnt == 1.0
    new_val = -sums / jnp.where(coeff == 0.0, 1.0, coeff)[:, None]
    safe_pos = jnp.where(solvable, pos, N)
    values = values.at[safe_pos].set(new_val, mode="drop")
    erased = erased.at[safe_pos].set(False, mode="drop")
    return values, erased


@partial(jax.jit, static_argnames=("iters",))
def peel_fixed_sparse(check_idx, check_coeff, values, erased, iters: int):
    """``iters`` sparse (neighbor-table) flooding rounds as one jitted loop.

    Operands are plain arrays (the table may be sharded over checks), so
    launch-layer steps can call this inside their own jit with explicit
    shardings; ``values`` (N, V), ``erased`` (N,) bool.
    """
    def body(_, carry):
        v, e = carry
        return peel_round_sparse(check_idx, check_coeff, v, e)

    values, erased = jax.lax.fori_loop(0, iters, body, (values, erased))
    return values, erased


# ------------------------------------------------- pattern-compiled replay


class PeelSchedule:
    """Pre-solved peeling elimination order for ONE ``(code, erasure)`` pair.

    The flooding trajectory — which check resolves which variable in which
    round — is a pure function of the code structure and the erasure mask,
    never of the payload values.  :func:`compile_peel_schedule` runs that
    trajectory ONCE symbolically (host-side numpy, to fixpoint) and records,
    per resolved variable: its flooding round (``offsets`` delimits the
    per-round segments, so replay parallelizes within a round), its gathered
    neighbor columns, and the pre-masked edge weights — under BOTH duplicate
    -check tie-break rules, since the existing backends differ:

    * ``idx_hi``/``w_hi``/``coeff_hi`` — HIGHEST check row wins, matching
      the single-pattern dense/sparse rounds (``.at[pos].set`` duplicate
      scatters are last-write-wins, and check rows scatter in ascending
      order);
    * ``idx_lo``/``w_lo``/``coeff_lo`` — LOWEST check row wins, matching
      the batch-major round's first-match candidate scan and the Pallas
      kernels' ``min``-row merges.

    Duplicate winners write consistent values (parity checks of one
    codeword), so the choice only pins f32 rounding — keeping both rules
    lets replay reproduce each backend family bit-for-bit.

    Because flooding is monotone (a round that resolves nothing ends the
    decode), the resolving rounds form a prefix: replay under a smaller
    round budget is simply a prefix slice of the same schedule.

    Instances hash/compare by IDENTITY (the arrays are frozen after
    construction).  The replay executors receive the schedule's numeric
    arrays as RUNTIME operands (:func:`_sched_ops`), so jit specializes on
    the per-round segment SHAPES only: patterns that resolve the same
    number of variables per round share one compiled executable, and XLA
    cannot constant-fold the replay arithmetic into different roundings
    than the flooding rounds it must match bit-for-bit.  That protection
    covers the library's own jitted executors; under a USER'S outer
    ``jax.jit`` the closed-over schedule arrays are necessarily trace
    constants, so the reciprocal fold may cost the last ulp on resolved
    values there (the erasure trajectory is exact regardless).
    """

    __slots__ = ("N", "r_max", "n_erased", "n_rounds", "n_resolved",
                 "fully_resolved", "offsets", "target",
                 "idx_lo", "w_lo", "coeff_lo",
                 "idx_hi", "w_hi", "coeff_hi", "mask_key", "_ops")

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"PeelSchedule(N={self.N}, n_erased={self.n_erased}, "
                f"n_resolved={self.n_resolved}, n_rounds={self.n_rounds}, "
                f"fully_resolved={self.fully_resolved})")


def erasure_mask_key(erased) -> bytes:
    """Canonical packed-bitmask key of a concrete erasure mask — the
    schedule-cache key and the schedule/mask consistency fingerprint."""
    e = np.asarray(erased, bool)
    return np.packbits(e).tobytes()


def compile_peel_schedule(code: LDPCCode, erased) -> PeelSchedule:
    """Symbolically solve the peeling decode for ``(code, erased)``.

    Runs the flooding schedule on the erasure mask alone (host-side numpy,
    no payload arithmetic) until fixpoint and returns the
    :class:`PeelSchedule` that :func:`peel_decode` et al. replay under
    ``backend="replay"``.  Work is O(rounds · edges) once per pattern;
    every replay of the result is O(resolved edges).
    """
    if not isinstance(code, LDPCCode):
        raise ValueError(
            "compile_peel_schedule needs an LDPCCode (neighbor table); got "
            f"{type(code).__name__!r}")
    if isinstance(erased, jax.core.Tracer):
        raise ValueError(
            "compile_peel_schedule needs a CONCRETE erasure mask — the "
            "schedule is solved host-side from the pattern. Under jit, "
            "solve outside (e.g. via repro.core.schedule_cache) and pass "
            "the schedule in as a static argument.")
    idx = np.asarray(code.check_idx)          # (p, r_max), sentinel N
    coeff = np.asarray(code.check_coeff)      # (p, r_max), 0-padded
    N = int(code.N)
    e0 = np.asarray(erased, bool)
    if e0.shape != (N,):
        raise ValueError(f"erased must be ({N},); got {e0.shape}")
    e = np.zeros(N + 1, bool)
    e[:N] = e0

    offsets = [0]
    tgt_parts: list[np.ndarray] = []
    lo_parts: list[np.ndarray] = []
    hi_parts: list[np.ndarray] = []
    while True:
        ne = e[idx]                           # (p, r_max)
        rows = np.flatnonzero(ne.sum(axis=1) == 1)
        if rows.size == 0:
            break
        slot = ne[rows].argmax(axis=1)
        tgts = idx[rows, slot]
        # Per duplicate-resolved variable: lowest and highest check row
        # (``rows`` ascends, so first/last occurrence = lowest/highest).
        uniq, first = np.unique(tgts, return_index=True)
        _, first_rev = np.unique(tgts[::-1], return_index=True)
        last = tgts.size - 1 - first_rev
        tgt_parts.append(uniq.astype(np.int32))
        lo_parts.append(rows[first].astype(np.int32))
        hi_parts.append(rows[last].astype(np.int32))
        offsets.append(offsets[-1] + uniq.size)
        e[uniq] = False

    def _cat(parts):
        return (np.concatenate(parts) if parts
                else np.zeros((0,), np.int32))

    target = _cat(tgt_parts)
    n = int(target.size)
    sched = PeelSchedule.__new__(PeelSchedule)
    sched.N = N
    sched.r_max = int(idx.shape[1])
    sched.n_erased = int(e0.sum())
    sched.n_rounds = len(offsets) - 1
    sched.n_resolved = n
    sched.fully_resolved = not e[:N].any()
    sched.offsets = np.asarray(offsets, np.int32)
    sched.target = target
    for rule, rows_all in (("lo", _cat(lo_parts)), ("hi", _cat(hi_parts))):
        nidx = idx[rows_all]                  # (n, r_max)
        ncoeff = coeff[rows_all]
        tslot = (nidx == target[:, None]).argmax(axis=1)
        # Known-neighbor weights exactly as the runtime rounds compute them
        # (coeff * (1 - erased)): the target slot is the ONLY erased
        # neighbor of a firing check, so the multiply — not an overwrite —
        # preserves signed zeros bit-for-bit.
        known_f = np.ones_like(ncoeff)
        known_f[np.arange(n), tslot] = 0.0
        setattr(sched, f"idx_{rule}", nidx.astype(np.int32))
        setattr(sched, f"w_{rule}", ncoeff * known_f)
        setattr(sched, f"coeff_{rule}", ncoeff[np.arange(n), tslot])
    sched.mask_key = erasure_mask_key(e0)
    sched._ops = {}
    return sched


# Unit roundoff the value contract charges per f32 operation: IEEE f32
# rounds to 2^-24; TPU matmuls at Precision.HIGHEST emulate f32 with
# several bf16 passes and stay within a few ulps of it.  2^-22 (4 ulps)
# covers both.
F32_OP_ERROR = 2.0 ** -22


def peel_error_bound(code: LDPCCode, erased, values, rounds: int | None = None,
                     *, input_error=None) -> np.ndarray:
    """Per-coordinate forward error bound of a peeled decode — THE value
    contract every backend is held to.

    Every backend follows the same erasure trajectory exactly; what may
    differ is f32 rounding, and peeling AMPLIFIES it along the chain: a
    check resolving ``c_t = -(Σ_s w_s c_s) / a`` turns its sources' errors
    ``ε_s`` into ``(Σ_s |w_s| (ε_s + γ|c_s|)) / |a| + u|c_t|`` with
    ``γ = (r_max + 2)·u`` for the products, the ``r_max``-term sum and the
    divide (``u`` = :data:`F32_OP_ERROR`), so a chain of small ``|a|``
    multiplies the error of its first link.  This walks the pattern's
    :func:`compile_peel_schedule` order on the host in float64 and returns
    that bound for every coordinate: ``input_error`` (default: one f32
    rounding of ``|values|``) on received symbols, the propagated bound on
    coordinates resolved within ``rounds`` (default: all), ``inf`` on
    those left erased.  Where duplicate checks resolve one coordinate, the
    larger of the lowest- and highest-row bounds is taken, covering both
    tie-break rules the backends use.

    ``values`` are the DECODED values ``(N,)`` or ``(N, V)`` (their
    magnitudes stand in for the exact ones); a decode is within contract
    when ``|decoded - exact| <= bound`` on every resolved coordinate.
    """
    sched = compile_peel_schedule(code, erased)
    mag = np.abs(np.asarray(values, np.float64))
    squeeze = mag.ndim == 1
    if squeeze:
        mag = mag[:, None]
    N, V = sched.N, mag.shape[1]
    u = F32_OP_ERROR
    gamma = (sched.r_max + 2) * u
    err = np.zeros((N + 1, V))               # row N: the sentinel column
    err[:N] = (u * mag if input_error is None
               else np.broadcast_to(np.abs(np.asarray(
                   input_error, np.float64)).reshape(N, -1), (N, V)))
    err[:N][np.asarray(erased, bool)] = np.inf
    mag = np.concatenate([mag, np.zeros((1, V))])
    n_rounds = (sched.n_rounds if rounds is None
                else min(int(rounds), sched.n_rounds))
    for k in range(n_rounds):
        s0, s1 = int(sched.offsets[k]), int(sched.offsets[k + 1])
        tgt = sched.target[s0:s1]
        bound = np.zeros((s1 - s0, V))
        for rule in ("lo", "hi"):
            idx = getattr(sched, f"idx_{rule}")[s0:s1]
            w = np.abs(getattr(sched, f"w_{rule}")[s0:s1])[:, :, None]
            a = np.abs(getattr(sched, f"coeff_{rule}")[s0:s1])[:, None]
            # w == 0 on the target slot (still erased: err = inf) and on
            # padding; mask so 0 * inf cannot poison the sum
            e_src = np.where(w > 0, err[idx], 0.0)
            src = w * (e_src + gamma * mag[idx])
            bound = np.maximum(bound, src.sum(axis=1) / a + u * mag[tgt])
        err[tgt] = bound
    out = err[:N]
    return out[:, 0] if squeeze else out


def _check_schedule(sched: PeelSchedule, code, erased) -> None:
    if not isinstance(sched, PeelSchedule):
        raise ValueError(f"schedule must be a PeelSchedule; got "
                         f"{type(sched).__name__!r}")
    N = code.N if isinstance(code, (LDPCCode, SeededLDPC)) else None
    if N is not None and sched.N != N:
        raise ValueError(f"schedule was solved for N={sched.N}, code has "
                         f"N={N}")
    # With a concrete mask the fingerprint check is cheap; under jit the
    # mask is a tracer and the caller (cache / driver) owns consistency.
    if not isinstance(erased, jax.core.Tracer):
        if sched.mask_key != erasure_mask_key(erased):
            raise ValueError(
                "schedule does not match the erasure mask being decoded "
                "(stale cache entry or wrong pattern)")


def _replay_rounds_used(sched: PeelSchedule, budget: int | jax.Array):
    """Round count matching the adaptive while_loop's stopping rule
    ``(d < budget) & progressed & e.any()``, from the schedule alone:
    0 if nothing was erased, else min(budget, R) when the pattern fully
    resolves in R rounds, else min(budget, R+1) — one probe round past the
    fixpoint observes no progress.  ``budget`` may be traced."""
    if sched.n_erased == 0:
        return jnp.int32(0)
    probe = sched.n_rounds + (0 if sched.fully_resolved else 1)
    b = jnp.asarray(budget, jnp.int32)
    return jnp.maximum(0, jnp.minimum(b, probe)).astype(jnp.int32)


def _sched_ops(sched: PeelSchedule, rule: str) -> tuple:
    """Per-round replay operands ``(nidx, w, coeff, target)`` as device
    arrays, built lazily once per (schedule, tie-break rule) and cached on
    the schedule.

    The executors take these as RUNTIME operands, never as jit constants:
    baked-in constants invite precision-changing folds (XLA rewrites
    divide-by-constant into multiply-by-reciprocal, breaking bit-parity
    with the flooding rounds' runtime divide), and operand-passing means
    jit specializes on segment shapes only, so recurring straggler
    patterns of the same size share one compiled executable.
    """
    ops = sched._ops.get(rule)
    if ops is None:
        off = sched.offsets
        idx = getattr(sched, f"idx_{rule}")
        w = getattr(sched, f"w_{rule}")
        cf = getattr(sched, f"coeff_{rule}")
        # ensure_compile_time_eval keeps these CONCRETE even when the
        # first use is under a caller's jit trace — otherwise jnp.asarray
        # lifts the host arrays to that trace's tracers and caching them
        # on the schedule would poison every later eager replay
        with jax.ensure_compile_time_eval():
            ops = tuple(
                (jnp.asarray(idx[s0:s1]), jnp.asarray(w[s0:s1]),
                 jnp.asarray(cf[s0:s1]), jnp.asarray(sched.target[s0:s1]))
                for s0, s1 in ((int(off[k]), int(off[k + 1]))
                               for k in range(sched.n_rounds)))
        sched._ops[rule] = ops
    return ops


def _replay_round(v, e, nidx, w, cf, tgt):
    """One replay round's arithmetic on the resolving checks only —
    exactly the flooding rounds' op sequence (:func:`_edge_sum` chain,
    then negate / guarded divide) restricted to ``len(tgt)`` rows."""
    dt = v.dtype
    v_pad = jnp.concatenate([v, jnp.zeros((1, v.shape[1]), dt)])
    nv = v_pad[nidx]                                     # (s, r_max, V)
    sums = _edge_sum(nv, w.astype(dt))
    cfd = cf.astype(dt)
    return -sums / jnp.where(cfd == 0.0, 1.0, cfd)[:, None]


@jax.jit
def _replay_fixed_ops(ops: tuple, values, erased):
    """Replay pre-sliced schedule rounds on one pattern.

    Mirrors :func:`peel_round_sparse`'s arithmetic exactly — the same
    :func:`_edge_sum` chain over the same r_max slots with the same
    pre-masked weights, restricted to the resolving checks ("high" winner
    = the duplicate scatter's last write) — so values are bit-identical
    to the sparse flooding decode while doing O(resolved edges) work with
    no while_loop or convergence mask.
    """
    v, e = values, erased
    for nidx, w, cf, tgt in ops:
        new_val = _replay_round(v, e, nidx, w, cf, tgt)
        v = v.at[tgt].set(new_val)
        e = e.at[tgt].set(False)
    return v, e


def _replay_fixed(sched: PeelSchedule, values, erased, rounds: int):
    return _replay_fixed_ops(_sched_ops(sched, "hi")[:rounds],
                             values, erased)


def _replay_slot_lo(slot_ops: tuple, v, e, budget):
    """One batch slot's replay mirroring :func:`peel_round_sparse_batch`'s
    arithmetic (the same :func:`_edge_sum` chain, "low" winner = the
    candidate scan's lowest-check-row first match).  ``budget`` is a
    traced per-slot round budget (writes beyond it are masked off — the
    state they would have read is still the correct prefix state), or
    None for the fixed-D batch decode."""
    for k, (nidx, w, cf, tgt) in enumerate(slot_ops):
        new_val = _replay_round(v, e, nidx, w, cf, tgt)
        if budget is None:
            v = v.at[tgt].set(new_val)
            e = e.at[tgt].set(False)
        else:
            apply = k < budget
            v = v.at[tgt].set(jnp.where(apply, new_val, v[tgt]))
            e = e.at[tgt].set(jnp.where(apply, False, e[tgt]))
    return v, e


@jax.jit
def _replay_batch_fixed_ops(ops_by_slot: tuple, values, erased):
    outs = [_replay_slot_lo(ops, values[b], erased[b], None)
            for b, ops in enumerate(ops_by_slot)]
    return (jnp.stack([o[0] for o in outs]),
            jnp.stack([o[1] for o in outs]))


def _replay_batch_fixed(scheds: tuple, values, erased, iters: int):
    ops = tuple(_sched_ops(s, "lo")[:min(iters, s.n_rounds)]
                for s in scheds)
    return _replay_batch_fixed_ops(ops, values, erased)


@jax.jit
def _replay_batch_adaptive_ops(ops_by_slot: tuple, values, erased, budgets):
    outs = [_replay_slot_lo(ops, values[b], erased[b], budgets[b])
            for b, ops in enumerate(ops_by_slot)]
    return (jnp.stack([o[0] for o in outs]),
            jnp.stack([o[1] for o in outs]))


def _replay_batch_adaptive(scheds: tuple, values, erased, budgets):
    ops = tuple(_sched_ops(s, "lo") for s in scheds)
    v, e = _replay_batch_adaptive_ops(ops, values, erased, budgets)
    d = jnp.stack([_replay_rounds_used(s, budgets[b])
                   for b, s in enumerate(scheds)])
    return v, e, d


def _replay_schedules(code, erased, schedules, B: int) -> tuple:
    """Per-slot schedules for the batched replay: validate the given ones
    or solve from the (necessarily concrete) per-slot masks."""
    if schedules is not None:
        scheds = tuple(schedules)
        if len(scheds) != B:
            raise ValueError(f"schedules must have length {B}; got "
                             f"{len(scheds)}")
        for b, s in enumerate(scheds):
            _check_schedule(s, code, erased[b])
        return scheds
    if isinstance(erased, jax.core.Tracer):
        raise ValueError(
            "backend='replay' under jit needs schedules= precompiled from "
            "the concrete per-slot masks (see repro.core.schedule_cache)")
    return tuple(compile_peel_schedule(code, erased[b]) for b in range(B))


# ----------------------------------------------------------------- dispatch


def _tile_knobs(code, bp, bv, vmem_budget_bytes):
    """Concrete (bp, bv) for the tiled kernels: ``bp`` sized from the VMEM
    budget unless given, ``bv`` defaulting to the kernels' 128 lanes."""
    if bp is None:
        bp = pick_tile_bp(code, vmem_budget_bytes=vmem_budget_bytes)
    return int(bp), int(bv) if bv is not None else 128


def _seeded_spec(code):
    """The hashable :class:`~repro.core.ldpc.SeededStructure` for a seeded
    code — materialized (``kind="ldpc-seeded"``), structure-only, or the
    bare structure itself (launch-layer callers hold no code object)."""
    if isinstance(code, SeededStructure):
        return code
    if isinstance(code, SeededLDPC):
        return code.structure
    return seeded_structure_of(code)


def _resolve_seeded_mode(seeded_mode: str, code, V: int, bp: int) -> str:
    """Resolve the ``seeded_mode`` knob to a concrete round implementation:
    "auto" applies the :func:`repro.core.hwcaps.pick_seeded_mode` crossover
    (gather iff the dense-tile round's modeled FLOPs exceed the platform's
    ``mxu_advantage ×`` the gather round's)."""
    if seeded_mode not in SEEDED_MODES:
        raise ValueError(f"unknown seeded_mode {seeded_mode!r}; "
                         f"want one of {SEEDED_MODES}")
    if seeded_mode == "auto":
        if jax.default_backend() == "tpu":
            # the gather round does not compile for TPU (kernel.py
            # interpret_only), whatever the FLOPs model says
            return "dense_tile"
        from repro.core.hwcaps import pick_seeded_mode

        return pick_seeded_mode(_seeded_spec(code), V, bp=bp)
    return seeded_mode


def peel_decode(
    code: LDPCCode | tuple[jax.Array, jax.Array],
    values: jax.Array,
    erased: jax.Array,
    iters: int,
    *,
    backend: str = "auto",
    bp: int | None = None,
    bv: int | None = None,
    vmem_budget_bytes: int | None = None,
    seeded_mode: str = "dense_tile",
    schedule: PeelSchedule | None = None,
    H: jax.Array | None = None,
) -> DecodeResult:
    """Run exactly ``iters`` flooding rounds (the paper's fixed-D decode).

    ``backend`` selects the implementation — see the module docstring for
    the full matrix.  The default ``"auto"`` keeps small/tuple inputs on the
    dense reference and routes large codes to the sparse neighbor-table
    round (or, on TPU, the fused one-kernel Pallas decode — resident H
    within ``vmem_budget_bytes``, check-axis tiled beyond it).  ``bp`` /
    ``bv`` are the tiled kernels' check/payload tile knobs (``bp`` defaults
    to :func:`pick_tile_bp`'s budget-sized tile).  ``seeded_mode``
    sub-dispatches the "pallas_seeded" round — "dense_tile" | "gather" |
    "auto" (hwcaps crossover); ignored by other backends.  ``schedule``
    feeds ``backend="replay"`` a pre-solved :class:`PeelSchedule` (e.g. a
    :mod:`repro.core.schedule_cache` hit); without it the pattern is
    solved on the fly, which requires a concrete ``erased``.
    """
    backend = resolve_backend(backend, code,
                              vmem_budget_bytes=vmem_budget_bytes)
    if schedule is not None and backend != "replay":
        raise ValueError("schedule= is only meaningful with "
                         "backend='replay'")
    v, squeeze = _expand(jnp.asarray(values))
    e = jnp.asarray(erased, bool)
    iters = int(iters)
    if backend == "replay":
        sched = (schedule if schedule is not None
                 else compile_peel_schedule(code, e))
        _check_schedule(sched, code, e)
        v, e = _replay_fixed(sched, v, e, min(iters, sched.n_rounds))
    elif backend == "sparse":
        idx, coeff = _tables(code)
        v, e = peel_fixed_sparse(idx, coeff, v, e, iters)
    elif backend == "pallas":
        from repro.kernels.ldpc_peel import (peel_decode_pallas,
                                             peel_decode_symbol_major_pallas)

        H = _dense_h(code, H, v.dtype)
        if _count_layout(backend, v.shape[1], code,
                         vmem_budget_bytes) == "symbol_major":
            v, e = peel_decode_symbol_major_pallas(
                H, v, e, iters, max_degree=code.check_idx.shape[1],
                bv=pick_tile_lanes(code, v.shape[1],
                                   vmem_budget_bytes=vmem_budget_bytes))
        else:
            v, e = peel_decode_pallas(H, v, e, iters)
    elif backend == "pallas_tiled":
        from repro.kernels.ldpc_peel import (peel_decode_edge_permuted_pallas,
                                             peel_decode_tiled_pallas)

        if _count_layout(backend, v.shape[1], code,
                         vmem_budget_bytes) == "edge_permuted":
            v, e = peel_decode_edge_permuted_pallas(code.edge_network, v, e,
                                                    iters)
        else:
            bp_, bv_ = _tile_knobs(code, bp, bv, vmem_budget_bytes)
            H = _dense_h(code, H, v.dtype)
            v, e = peel_decode_tiled_pallas(H, v, e, iters, bp=bp_, bv=bv_)
    elif backend == "pallas_seeded":
        from repro.kernels.ldpc_peel import peel_decode_seeded_pallas

        bp_, bv_ = _tile_knobs(code, bp, bv, vmem_budget_bytes)
        mode = _resolve_seeded_mode(seeded_mode, code, v.shape[1], bp_)
        _count_layout(backend, v.shape[1], code, vmem_budget_bytes)
        v, e = peel_decode_seeded_pallas(_seeded_spec(code), v, e, iters,
                                         bp=bp_, bv=bv_, mode=mode)
    else:
        H, Hb = _mats(code, v.dtype, H)
        v, e = peel_fixed_dense(H, Hb, v, e, iters)
    if squeeze:
        v = v[:, 0]
    return DecodeResult(v, e, jnp.int32(iters))


# ------------------------------------------------------------- batched axis


@partial(jax.jit, static_argnames=("iters",))
def _peel_fixed_dense_batch(H, Hb, values, erased, iters: int):
    # vmap the whole fixed-D loop; H/Hb broadcast (loaded once, shared) and
    # the per-round matvecs batch into (p, N) @ (N, B) GEMMs.
    return jax.vmap(lambda v, e: peel_fixed_dense(H, Hb, v, e, iters))(
        values, erased)


def peel_round_sparse_batch(check_idx, check_coeff, var_idx, vb, eb):
    """One flooding round for B independent erasure patterns, scatter-free.

    Batch-minor layout: ``vb (N+1, B, V)`` values (one zero sentinel row,
    V payload lanes per pattern), ``eb (N+1, B)`` f32 0/1 erasure flags —
    neighbor gathers then move contiguous rows instead of strided scalars.

    Check side: a solvable check has EXACTLY one erased neighbour, so the
    masked sums ``Σ idx·e`` / ``Σ coeff·e`` *are* its resolved index and
    coefficient — exact in f32 (small integers / single surviving term), no
    argmax, and bit-identical solvability decisions to
    :func:`peel_round_sparse`.  The V payload lanes of one pattern share a
    trajectory, so ALL structure work (cnt/pos/coeff, solvability, the
    candidate-match masks) is computed ONCE per pattern on the ``(·, B)``
    erasure flags and broadcast over V — only the value sums and the
    resolved-value writes touch the ``(·, B, V)`` payload.

    Variable side: XLA's scatter is the slow op on CPU (~70 ns/element,
    serialized); instead each variable GATHERS its ≤ l_max candidate
    resolutions through the column table ``var_idx (N, l_max)``
    (:attr:`LDPCCode.var_idx`) and keeps the lowest-row match.  Checks that
    resolve the same coordinate write consistent values (parity checks of
    one codeword), so the choice only pins f32 rounding.
    """
    N = vb.shape[0] - 1
    dt = vb.dtype
    ne = eb[check_idx]                              # (p, r_max, B)
    nv = vb[check_idx]                              # (p, r_max, B, V)
    cnt = ne.sum(axis=1)                            # (p, B) — exact counts
    c3 = check_coeff.astype(dt)[:, :, None]
    known = (1.0 - ne) * c3                         # (p, r_max, B)
    sums = _edge_sum(nv, known)                     # (p, B, V)
    posf = (check_idx.astype(dt)[:, :, None] * ne).sum(axis=1)
    coeff = (c3 * ne).sum(axis=1)                   # (p, B)
    solvable = cnt == 1.0
    new_val = -sums / jnp.where(coeff == 0.0, 1.0, coeff)[..., None]
    res_pos = jnp.where(solvable, posf.astype(jnp.int32), N)    # (p, B)

    B, V = vb.shape[1], vb.shape[2]
    rp_pad = jnp.concatenate([res_pos, jnp.full((1, B), N, jnp.int32)])
    nv_pad = jnp.concatenate([new_val, jnp.zeros((1, B, V), dt)])
    cand_pos = rp_pad[var_idx]                      # (N, l_max, B)
    cand_val = nv_pad[var_idx]                      # (N, l_max, B, V)
    me = jax.lax.broadcasted_iota(jnp.int32, cand_pos.shape, 0)
    match = cand_pos == me                          # (N, l_max, B)
    resolved = jnp.zeros((N, B), bool)
    val = jnp.zeros((N, B, V), dt)
    for t in range(match.shape[1]):                 # l_max is small & static
        m = match[:, t]
        val = jnp.where((m & ~resolved)[..., None], cand_val[:, t], val)
        resolved = resolved | m
    vb = vb.at[:N].set(jnp.where(resolved[..., None], val, vb[:N]))
    eb = eb.at[:N].set(jnp.where(resolved, 0.0, eb[:N]))
    return vb, eb


@partial(jax.jit, static_argnames=("iters",))
def _peel_fixed_sparse_batch(check_idx, check_coeff, var_idx, values, erased,
                             iters: int):
    """values (B, N, V), erased (B, N) → fixed-D batch-major sparse decode.

    The erasure state is carried once per pattern (``(N+1, B)``) while the
    payload keeps its own V axis (``(N+1, B, V)``), so the check-side
    structure work runs once per pattern and only the value arithmetic
    scales with V — see :func:`peel_round_sparse_batch`.
    """
    B, N, V = values.shape
    vb = jnp.concatenate([jnp.transpose(values, (1, 0, 2)),
                          jnp.zeros((1, B, V), values.dtype)])  # (N+1, B, V)
    eb = jnp.concatenate([erased.T.astype(values.dtype),
                          jnp.zeros((1, B), values.dtype)])     # (N+1, B)

    def body(_, carry):
        return peel_round_sparse_batch(check_idx, check_coeff, var_idx,
                                       *carry)

    vb, eb = jax.lax.fori_loop(0, iters, body, (vb, eb))
    out_v = jnp.transpose(vb[:N], (1, 0, 2))
    out_e = eb[:N].T > 0.0
    return out_v, out_e


def peel_decode_batch(
    code: LDPCCode | tuple[jax.Array, jax.Array],
    values: jax.Array,
    erased: jax.Array,
    iters: int,
    *,
    backend: str = "auto",
    bp: int | None = None,
    bv: int | None = None,
    vmem_budget_bytes: int | None = None,
    seeded_mode: str = "dense_tile",
    schedules=None,
    H: jax.Array | None = None,
) -> DecodeResult:
    """Decode ``B`` INDEPENDENT erasure patterns in one launch.

    ``values`` is ``(B, N)`` or ``(B, N, V)``; ``erased`` is ``(B, N)``
    bool — one straggler realization per batch element.  Each element is
    decoded exactly as :func:`peel_decode` would decode it alone (identical
    trajectories); the batch axis only amortizes dispatch and keeps the
    code's structure (H / neighbor table) loaded once:

    * "dense" / "sparse": the fixed-D loop is ``vmap``-ed over the pattern
      axis with the code operands broadcast;
    * "pallas": ``peel_decode_batch_pallas`` — ONE ``pallas_call`` whose
      grid runs over the batch with the H tile resident in VMEM and shared;
    * "pallas_tiled": ``peel_decode_batch_tiled_pallas`` — one launch, H
      streamed over check tiles per slot (beyond the VMEM cap);
    * "replay": per-slot pre-solved schedules (``schedules=``, one
      :class:`PeelSchedule` per slot, or solved on the fly from concrete
      masks) replayed as straight-line gather/FMA work.

    This is the serving primitive: many concurrent coded matvec/gradient
    queries, each with its own straggler mask, one decode launch
    (see :mod:`repro.serving.coded_queries`).
    """
    backend = resolve_backend(backend, code,
                              vmem_budget_bytes=vmem_budget_bytes)
    if schedules is not None and backend != "replay":
        raise ValueError("schedules= is only meaningful with "
                         "backend='replay'")
    v = jnp.asarray(values)
    if v.ndim not in (2, 3):
        raise ValueError(f"batched values must be (B, N) or (B, N, V); "
                         f"got shape {v.shape}")
    squeeze = v.ndim == 2
    if squeeze:
        v = v[:, :, None]
    e = jnp.asarray(erased, bool)
    iters = int(iters)
    if backend == "replay":
        scheds = _replay_schedules(code, e, schedules, v.shape[0])
        v, e = _replay_batch_fixed(scheds, v, e, iters)
    elif backend == "sparse":
        idx, coeff = _tables(code)
        v, e = _peel_fixed_sparse_batch(idx, coeff,
                                        jnp.asarray(code.var_idx), v, e,
                                        iters)
    elif backend == "pallas":
        from repro.kernels.ldpc_peel import peel_decode_batch_pallas

        H = _dense_h(code, H, v.dtype)
        v, e = peel_decode_batch_pallas(H, v, e, iters)
    elif backend == "pallas_tiled":
        from repro.kernels.ldpc_peel import peel_decode_batch_tiled_pallas

        bp_, bv_ = _tile_knobs(code, bp, bv, vmem_budget_bytes)
        H = _dense_h(code, H, v.dtype)
        v, e = peel_decode_batch_tiled_pallas(H, v, e, iters, bp=bp_, bv=bv_)
    elif backend == "pallas_seeded":
        from repro.kernels.ldpc_peel import peel_decode_batch_seeded_pallas

        bp_, bv_ = _tile_knobs(code, bp, bv, vmem_budget_bytes)
        mode = _resolve_seeded_mode(seeded_mode, code, v.shape[2], bp_)
        v, e = peel_decode_batch_seeded_pallas(_seeded_spec(code), v, e,
                                               iters, bp=bp_, bv=bv_,
                                               mode=mode)
    else:
        H, Hb = _mats(code, v.dtype, H)
        v, e = _peel_fixed_dense_batch(H, Hb, v, e, iters)
    if squeeze:
        v = v[:, :, 0]
    return DecodeResult(v, e, jnp.int32(iters))


# ----------------------------------------------------------------- adaptive


@partial(jax.jit, static_argnames=("max_iters",))
def _peel_adaptive(H, Hb, values, erased, max_iters: int):
    def cond(carry):
        _, e, d, progressed = carry
        return (d < max_iters) & progressed & e.any()

    def body(carry):
        v, e, d, _ = carry
        v2, e2 = peel_round(H, Hb, v, e)
        return v2, e2, d + 1, (e2 != e).any()

    v, e, d, _ = jax.lax.while_loop(
        cond, body, (values, erased, jnp.int32(0), jnp.bool_(True))
    )
    return v, e, d


@partial(jax.jit, static_argnames=("max_iters",))
def _peel_adaptive_sparse(check_idx, check_coeff, values, erased, max_iters: int):
    def cond(carry):
        _, e, d, progressed = carry
        return (d < max_iters) & progressed & e.any()

    def body(carry):
        v, e, d, _ = carry
        v2, e2 = peel_round_sparse(check_idx, check_coeff, v, e)
        return v2, e2, d + 1, (e2 != e).any()

    v, e, d, _ = jax.lax.while_loop(
        cond, body, (values, erased, jnp.int32(0), jnp.bool_(True))
    )
    return v, e, d


def peel_decode_adaptive(
    code: LDPCCode | tuple[jax.Array, jax.Array],
    values: jax.Array,
    erased: jax.Array,
    max_iters: int | None = None,
    *,
    backend: str = "auto",
    bp: int | None = None,
    bv: int | None = None,
    vmem_budget_bytes: int | None = None,
    seeded_mode: str = "dense_tile",
    schedule: PeelSchedule | None = None,
    H: jax.Array | None = None,
) -> DecodeResult:
    """Decode until fixpoint (no check resolves) or ``max_iters`` rounds.

    This is the "decoding effort adapts to the number of stragglers" mode:
    with few erasures the loop exits after 1-2 rounds.  ``backend="pallas"``
    runs the early-exit loop INSIDE the fused kernel (one launch, in-kernel
    while_loop on the unresolved count) — same trajectory and round count as
    the dense/sparse while_loops; ``"pallas_tiled"`` additionally stops the
    H streaming at the early exit.  ``backend="replay"`` already knows the
    fixpoint from the schedule, so "adaptivity" costs nothing: the replay
    is sliced to ``min(max_iters, R)`` rounds and the round count is
    computed from the schedule, matching the while_loop's stopping rule
    (including the one probe round a non-fully-resolving pattern pays).
    """
    backend = resolve_backend(backend, code, adaptive=True,
                              vmem_budget_bytes=vmem_budget_bytes)
    if schedule is not None and backend != "replay":
        raise ValueError("schedule= is only meaningful with "
                         "backend='replay'")
    if max_iters is None:
        max_iters = int(code.N if isinstance(code, (LDPCCode, SeededLDPC))
                        else code[0].shape[1])
    v, squeeze = _expand(jnp.asarray(values))
    e = jnp.asarray(erased, bool)
    if backend == "replay":
        sched = (schedule if schedule is not None
                 else compile_peel_schedule(code, e))
        _check_schedule(sched, code, e)
        v, e = _replay_fixed(sched, v, e,
                             min(int(max_iters), sched.n_rounds))
        d = _replay_rounds_used(sched, int(max_iters))
    elif backend == "sparse":
        idx, coeff = _tables(code)
        v, e, d = _peel_adaptive_sparse(idx, coeff, v, e, int(max_iters))
    elif backend == "pallas":
        from repro.kernels.ldpc_peel import peel_decode_adaptive_pallas

        H = _dense_h(code, H, v.dtype)
        v, e, d = peel_decode_adaptive_pallas(H, v, e, int(max_iters))
    elif backend == "pallas_tiled":
        from repro.kernels.ldpc_peel import peel_decode_adaptive_tiled_pallas

        bp_, bv_ = _tile_knobs(code, bp, bv, vmem_budget_bytes)
        H = _dense_h(code, H, v.dtype)
        v, e, d = peel_decode_adaptive_tiled_pallas(H, v, e, int(max_iters),
                                                    bp=bp_, bv=bv_)
    elif backend == "pallas_seeded":
        from repro.kernels.ldpc_peel import peel_decode_adaptive_seeded_pallas

        bp_, bv_ = _tile_knobs(code, bp, bv, vmem_budget_bytes)
        mode = _resolve_seeded_mode(seeded_mode, code, v.shape[1], bp_)
        v, e, d = peel_decode_adaptive_seeded_pallas(
            _seeded_spec(code), v, e, int(max_iters), bp=bp_, bv=bv_,
            mode=mode)
    else:
        H, Hb = _mats(code, v.dtype, H)
        v, e, d = _peel_adaptive(H, Hb, v, e, int(max_iters))
    if squeeze:
        v = v[:, 0]
    return DecodeResult(v, e, d)


# -------------------------------------------------- batched x adaptive axis


@jax.jit
def _peel_adaptive_dense_batch(H, Hb, values, erased, budgets):
    """Per-slot early-exit dense decode: vmap of the adaptive while_loop.

    JAX's while_loop batching rule gives exactly the per-slot semantics: the
    lowered loop runs while ANY slot's predicate holds, and a slot whose own
    predicate is false has its carry frozen via select — so each slot's
    (values, erased, rounds) trajectory is the one the sequential adaptive
    decode produces under its own ``budgets[b]`` round budget.
    """
    def one(v, e, budget):
        def cond(carry):
            _, e_, d, progressed = carry
            return (d < budget) & progressed & e_.any()

        def body(carry):
            v_, e_, d, _ = carry
            v2, e2 = peel_round(H, Hb, v_, e_)
            return v2, e2, d + 1, (e2 != e_).any()

        return jax.lax.while_loop(
            cond, body, (v, e, jnp.int32(0), jnp.bool_(True)))[:3]

    return jax.vmap(one)(values, erased, budgets)


@jax.jit
def _peel_adaptive_sparse_batch(check_idx, check_coeff, var_idx, values,
                                erased, budgets):
    """Per-slot early-exit decode on the scatter-free batch-major round.

    One while_loop advances ALL still-active slots a round at a time; a
    per-slot active mask ``(d < budget) & progressed & any_erased`` freezes
    converged slots' columns (select — their lanes carry no further work or
    rounding churn) and the loop exits as soon as every slot is done, so a
    batch of light stragglers costs 1-2 rounds regardless of the budget.
    Layout and round semantics are exactly :func:`peel_round_sparse_batch`'s
    (values (B, N, V), erased (B, N) bool; the V lanes of one slot share
    the trajectory, and all structure work runs once per slot).  Returns
    (values, erased, rounds (B,)).
    """
    B, N, V = values.shape
    dt = values.dtype
    vb = jnp.concatenate([jnp.transpose(values, (1, 0, 2)),
                          jnp.zeros((1, B, V), dt)])         # (N+1, B, V)
    eb = jnp.concatenate([erased.T.astype(dt),
                          jnp.zeros((1, B), dt)])            # (N+1, B)
    budgets = budgets.astype(jnp.int32)

    def slot_erased_any(eb_):
        return eb_[:N].sum(axis=0) > 0.0                     # (B,) bool

    # The per-slot predicate ``(d < budget) & progressed & any_erased`` is
    # carried as one ACTIVE mask (slots only ever deactivate), so each round
    # costs exactly one masked-round + two (N, B) reductions — the cond is a
    # free ``active.any()``.
    def cond(carry):
        return carry[3].any()

    def body(carry):
        vb_, eb_, d, active = carry
        vb2, eb2 = peel_round_sparse_batch(check_idx, check_coeff, var_idx,
                                           vb_, eb_)
        changed = (eb2[:N] != eb_[:N]).any(axis=0)           # (B,)
        vb_ = jnp.where(active[None, :, None], vb2, vb_)
        eb_ = jnp.where(active[None, :], eb2, eb_)
        d = jnp.where(active, d + 1, d)
        active = (active & (d < budgets) & changed
                  & slot_erased_any(eb_))
        return vb_, eb_, d, active

    active0 = (budgets > 0) & slot_erased_any(eb)
    vb, eb, d, _ = jax.lax.while_loop(
        cond, body, (vb, eb, jnp.zeros((B,), jnp.int32), active0))
    out_v = jnp.transpose(vb[:N], (1, 0, 2))
    out_e = eb[:N].T > 0.0
    return out_v, out_e, d


def peel_decode_batch_adaptive(
    code: LDPCCode | tuple[jax.Array, jax.Array],
    values: jax.Array,
    erased: jax.Array,
    max_iters: int | None = None,
    *,
    backend: str = "auto",
    budgets: jax.Array | None = None,
    bp: int | None = None,
    bv: int | None = None,
    vmem_budget_bytes: int | None = None,
    seeded_mode: str = "dense_tile",
    schedules=None,
    H: jax.Array | None = None,
) -> DecodeResult:
    """Decode ``B`` independent patterns with PER-SLOT early exit, one launch.

    The batched form of :func:`peel_decode_adaptive`: every slot follows its
    own stopping rule (no progress, nothing erased, or its round budget
    exhausted) and reports its own round count — ``rounds_used`` is the
    per-slot ``(B,) int32`` vector.  A slot full of light stragglers stops
    after 1-2 rounds while a heavy slot keeps peeling; no slot's trajectory
    depends on any other slot's.  Trajectory parity with the sequential
    adaptive decode is exact (same erasure masks and round counts,
    bit-for-bit); values agree up to f32 summation order, as on the fixed-D
    batch axis.

    ``budgets`` optionally gives each slot its own round budget
    ``(B,) int`` — a TRACED operand (varying budgets launch-to-launch never
    recompiles), clamped nowhere: a slot with budget 0 is returned
    untouched with 0 rounds.  Without it every slot gets ``max_iters``
    (default ``N``).  This is the primitive behind continuous-admission
    serving (:mod:`repro.serving.coded_queries`): in-flight slots carry
    their remaining budgets across chunked launches.

    ``backend="replay"`` takes per-slot pre-solved ``schedules=`` (or
    solves them from concrete masks); budgets stay traced — writes past a
    slot's budget are masked off and the per-slot round counts come from
    the schedules.
    """
    backend = resolve_backend(backend, code, adaptive=True,
                              vmem_budget_bytes=vmem_budget_bytes)
    if schedules is not None and backend != "replay":
        raise ValueError("schedules= is only meaningful with "
                         "backend='replay'")
    v = jnp.asarray(values)
    if v.ndim not in (2, 3):
        raise ValueError(f"batched values must be (B, N) or (B, N, V); "
                         f"got shape {v.shape}")
    squeeze = v.ndim == 2
    if squeeze:
        v = v[:, :, None]
    e = jnp.asarray(erased, bool)
    B = v.shape[0]
    if max_iters is None:
        max_iters = int(code.N if isinstance(code, (LDPCCode, SeededLDPC))
                        else code[0].shape[1])
    if budgets is None:
        budgets = jnp.full((B,), int(max_iters), jnp.int32)
    else:
        budgets = jnp.asarray(budgets, jnp.int32)
        if budgets.shape != (B,):
            raise ValueError(f"budgets must be ({B},); got {budgets.shape}")
    if backend == "replay":
        scheds = _replay_schedules(code, e, schedules, B)
        v, e, d = _replay_batch_adaptive(scheds, v, e, budgets)
    elif backend == "sparse":
        idx, coeff = _tables(code)
        v, e, d = _peel_adaptive_sparse_batch(idx, coeff,
                                              jnp.asarray(code.var_idx),
                                              v, e, budgets)
    elif backend == "pallas":
        from repro.kernels.ldpc_peel import peel_decode_batch_adaptive_pallas

        H = _dense_h(code, H, v.dtype)
        v, e, d = peel_decode_batch_adaptive_pallas(H, v, e, budgets)
    elif backend == "pallas_tiled":
        from repro.kernels.ldpc_peel import (
            peel_decode_batch_adaptive_tiled_pallas)

        bp_, bv_ = _tile_knobs(code, bp, bv, vmem_budget_bytes)
        H = _dense_h(code, H, v.dtype)
        v, e, d = peel_decode_batch_adaptive_tiled_pallas(H, v, e, budgets,
                                                          bp=bp_, bv=bv_)
    elif backend == "pallas_seeded":
        from repro.kernels.ldpc_peel import (
            peel_decode_batch_adaptive_seeded_pallas)

        bp_, bv_ = _tile_knobs(code, bp, bv, vmem_budget_bytes)
        mode = _resolve_seeded_mode(seeded_mode, code, v.shape[2], bp_)
        v, e, d = peel_decode_batch_adaptive_seeded_pallas(
            _seeded_spec(code), v, e, budgets, bp=bp_, bv=bv_, mode=mode)
    else:
        H, Hb = _mats(code, v.dtype, H)
        v, e, d = _peel_adaptive_dense_batch(H, Hb, v, e, budgets)
    if squeeze:
        v = v[:, :, 0]
    return DecodeResult(v, e, d)


def erased_after(code: LDPCCode, erased: np.ndarray, iters: int) -> np.ndarray:
    """Structure-only decode: which coordinates remain erased after D rounds.

    Used by tests and by the density-evolution comparison; does not touch the
    payload values.
    """
    dummy = jnp.zeros((code.N,), jnp.float32)
    res = peel_decode(code, dummy, jnp.asarray(erased, bool), iters)
    return np.asarray(res.erased)


def _float_dtype(dtype):
    return dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.float32


def _dense_h(code, H, dtype) -> jax.Array:
    """The materialized parity-check matrix for the dense / resident /
    tiled backends: the caller's runtime operand ``H`` when given, else
    ``code.H``.  A jitted caller passes ``H`` in so the ``(p, N)`` matrix
    is an argument of its program rather than a constant embedded in it
    (512 MiB at N = 16384)."""
    return jnp.asarray(code.H if H is None else H, _float_dtype(dtype))


def _mats(code, dtype, H=None) -> tuple[jax.Array, jax.Array]:
    if H is not None:
        H = _dense_h(code, H, dtype)
        return H, H != 0.0
    if isinstance(code, LDPCCode):
        H = jnp.asarray(code.H, dtype=_float_dtype(dtype))
        Hb = jnp.asarray(code.H_mask)
    else:
        H, Hb = code
        H = jnp.asarray(H)
        Hb = jnp.asarray(Hb, bool)
    return H, Hb


def _tables(code: LDPCCode) -> tuple[jax.Array, jax.Array]:
    return jnp.asarray(code.check_idx), jnp.asarray(code.check_coeff)
