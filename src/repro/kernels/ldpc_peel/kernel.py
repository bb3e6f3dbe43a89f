"""LDPC peeling-decoder Pallas TPU kernels.

Kernel families (all built from ONE shared flooding-round implementation —
see :func:`_check_tile_proposal` / :func:`_resident_round` /
:func:`_streamed_round` and the two loop drivers :func:`_fixed_loop` /
:func:`_adaptive_loop`):

* :func:`check_pass` — the fused check-node pass of ONE flooding round
  (kept as the building block for the per-round path and its tests);
* resident-H fused decodes — the whole decode in ONE ``pallas_call`` with
  the ``(p, N)`` H tile loaded into VMEM once and kept resident:
  :func:`decode_fused` (fixed-``D``), :func:`decode_fused_batch` (``B``
  independent erasure patterns, grid over the batch, H shared),
  :func:`decode_fused_adaptive` (early-exit in-kernel ``while_loop``), and
  :func:`decode_fused_batch_adaptive` (per-slot ``while_loop`` with a
  TRACED per-slot round budget).  These are the fast path while the
  kernel's whole working set fits in VMEM (see
  ``core/decoder.vmem_bytes_estimate``).
* check-axis-TILED fused decodes — the same four variants with H living in
  HBM (``memory_space=ANY``) and streamed tile-by-tile over the CHECK axis
  through a double-buffered VMEM scratch (``(2, bp, N)`` slots + DMA
  semaphores), while the lane-major ``(bv, N)`` value carry stays in VMEM
  as the loop carry: :func:`decode_fused_tiled`,
  :func:`decode_fused_batch_tiled`,
  :func:`decode_fused_adaptive_tiled`,
  :func:`decode_fused_batch_adaptive_tiled`.  This removes the
  whole-H-in-VMEM cap (N ≲ 2048 f32) — problem size is bounded by HBM, not
  one core's VMEM; the VMEM cost is ``2·bp·N`` stream slots plus the value
  carry, independent of ``p``.

* :func:`decode_symbol_major` — a solved fixed-``D`` trajectory applied
  to a wide payload in its own ``(N, V)`` layout (code axis on sublanes,
  payload on lanes): no rounds, no H operand, no transpose and no
  128-lane padding of the code axis.  ops.py solves the trajectory once
  per call on H and the mask; the kernel streams ``(N, bv)`` lane tiles,
  copies each, zeroes its erased rows and sets each resolved row to its
  winning check's weighted sum of the other rows, in round order.  The
  schedule rides in SMEM as scalar prefetch and rows are addressed at
  dynamic sublane offsets, which Mosaic lowers (the replay kernel's value
  gathers it does not).  ``core/decoder.decode_layout`` takes it for
  ``V >= 512`` lanes (measured crossover near 340 lanes on one v5e);
  below that the lane-major :func:`decode_fused` stays.

* :func:`decode_edge_permuted` — a fixed-``D`` flooding decode on the
  code's EDGE array, with no H operand: the edges are laid out
  variable-major and check-major as slot-major planes of whole vregs, a
  check's (a variable's) reduction is elementwise over its planes, and the
  only data movement is the permutation between the two layouts, applied
  by a Beneš network routed on the host (``core/benes.py``).  Each stage
  is an XOR-partner exchange: two ``pltpu.roll``s and two selects inside a
  vreg on bits 0-9 (lanes, then sublanes), a select between two vregs
  from bit 10 up.  The switch bits ride as one int32 word per slot; the
  stages run reversed apply the inverse.  All rounds run in one launch,
  the carry in VMEM, and stop at the first round that resolves nothing
  (every later round would leave the state as it is).  ``core/decoder.decode_layout`` takes it on TPU for
  the fixed-D decode of a narrow payload that ``auto`` would stream H for.

The in-kernel "scatter" is expressed MXU-style: the per-check resolution
one-hot ``(bp, N)`` becomes the right operand of a matmul that moves each
resolved coordinate's new value into place — TPUs have no efficient
in-kernel scatter, but a ``(BV, bp) @ (bp, N)`` dot is native.  Checks
that resolve the same coordinate in the same round write consistent
values (they are parity checks of one codeword); the kernel
deterministically keeps the lowest-index check's value.  The tiled round
preserves that rule exactly: tiles are
processed in ascending check order and a coordinate takes the FIRST tile's
resolution (within a tile, the lowest row — so the merge winner is the
globally lowest check row, the same check the resident merge picks), and
every tile's proposal is computed against the ROUND-START state, so the
tiled schedule is still flooding, not layered.  Erasure trajectories are
therefore bit-identical across resident/tiled; values agree to f32
summation order (XLA may block a tile-shaped row-sum reduction differently
than the whole-H one).

TPU notes:
  * the flooding kernels carry values LANE-MAJOR: payload ``(V, N)`` with
    the code axis on lanes and payload rows on sublanes, erasure mask
    ``(1, N)`` — a scalar payload costs one 8-row tile instead of an
    ``(N, 128)`` lane-padded column (8 MiB at N = 16384, which overran
    VMEM and took minutes to compile); the ops.py wrappers transpose once
    at entry and exit;
  * all contractions run at ``Precision.HIGHEST`` (f32 on the MXU);
  * pos is computed with broadcasted_iota + max (no 1-D iota on TPU);
  * 1-D per-check outputs are materialized as (BP, 1) tiles (TPU wants >=2D);
  * resident grids re-map the same H block at every step, so H is fetched
    once and stays resident; the erasure trajectory depends only on H and
    the initial mask, so grid steps sharing a pattern recompute the
    identical trajectory and rewrite shared outputs consistently
    (benign — the grid is sequential on TPU);
  * tiled kernels stream H with ``pltpu.make_async_copy``: tile ``j+1``'s
    DMA is started before waiting on tile ``j`` (double buffering), and the
    pipeline runs on a GLOBAL tile counter so tile 0 of round ``t+1`` is
    prefetched during the LAST tile of round ``t`` (cross-round prefetch —
    the double buffer never resets at a round boundary); ``bp``/``bv``
    tuning on real TPUs is the recorded follow-on (ROADMAP);
  * off-TPU everything runs in interpret mode (correct but not fast),
    including the DMA pipeline.

SEEDED kernels (``decode_seeded*``): the same four decode contracts with
NO H operand at all — each ``bp x N`` tile is regenerated in-register
inside the round from the code's counter-based seed
(:class:`repro.core.ldpc.SeededStructure`, passed as a STATIC argument so
the per-layer affine constants compile into the kernel).  The jnp tile
generator :func:`seeded_h_tile` is bit-exact against the NumPy reference
``repro.core.ldpc.seeded_h_rows`` — every step is 32-bit integer
arithmetic or exact-in-f32 float math — so seeded trajectories are
bit-identical to every materialized backend on the same code, while the
operand traffic for H drops to zero bytes.

Each seeded kernel takes a static ``mode`` selecting HOW the round is
computed (the trajectory is identical either way):

* ``mode="dense_tile"`` (default) — regenerate the dense ``(bp, N)`` tile
  and reuse the tiled round's MXU matmuls on it: O(p·N) FLOPs per round.
* ``mode="gather"`` — never build the tile.  The check pass generates only
  the ``r`` (column, weight) pairs per check row from the seed and computes
  cnt/pos/coeff/sums as ``r`` gathers + a static segment-sum
  (:func:`_seeded_gather_round`); the variable pass inverts the layered
  affine permutations (a per-layer modular inverse, compiled in) so each
  column finds its ``l`` candidate check rows by direct index arithmetic —
  no scatter, no one-hot.  O(p·r + N·l·p/bp) FLOPs per round, an ~N/r
  compute win over the dense tile.  All solvability quantities are
  integer-exact, and the first-match/first-tile-wins merges reproduce the
  lowest-check-row tie-break, so gather-mode ERASURE TRAJECTORIES are
  bit-identical to dense-tile (and hence to every materialized backend);
  VALUES agree to f32 summation order (r-term draw-order sums vs tile dot
  reductions), the same caveat that already distinguishes resident from
  tiled.  The gathers are expressed as jnp ``take``s — exact in interpret
  mode everywhere; tuning their lowering on real TPU rides the ROADMAP
  item 5 profiling pass.

:func:`encode_seeded_fused` is the encode-side twin: one ``pallas_call``
that regenerates seeded-LDGM GENERATOR rows in-register (systematic +
sorted parity draws, an odd-even transposition network standing in for the
host-side argsort) and applies them to the payload as a sequential
gather-FMA — bit-identical to ``repro.core.encoding.gather_encode`` over
``seeded_generator_rows`` tables, with zero table operand traffic.  The
row offset is a TRACED scalar so sharded workers can encode their row
slice under ``shard_map`` without per-shard recompilation.

:func:`decode_replay` is the pattern-compiled fast path: it takes a PACKED
:class:`repro.core.decoder.PeelSchedule` (per-round sentinel-padded entry
segments) and applies the whole pre-solved elimination order in ONE
``pallas_call`` — no flooding loop, no convergence mask, no H operand;
work is O(schedule entries · r_max), i.e. proportional to the resolved
edges, not rounds × p·r.  Its edge-sum duplicates the decoder's
scan-boundary compensated chain (:func:`_replay_edge_sum`), so replayed
values are bit-identical to the ``backend="replay"`` executors and hence
to the sparse flooding decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.benes import stage_bits
from repro.kernels import detect_interpret

__all__ = ["check_pass", "decode_fused", "decode_fused_batch",
           "decode_fused_adaptive", "decode_fused_batch_adaptive",
           "decode_fused_tiled", "decode_fused_batch_tiled",
           "decode_fused_adaptive_tiled", "decode_fused_batch_adaptive_tiled",
           "decode_seeded", "decode_seeded_batch", "decode_seeded_adaptive",
           "decode_seeded_batch_adaptive", "seeded_h_tile",
           "encode_seeded_fused", "decode_replay", "decode_symbol_major",
           "decode_edge_permuted", "detect_interpret",
           "interpret_only"]

SEEDED_MODES = ("dense_tile", "gather")

_HIGH = jax.lax.Precision.HIGHEST
# dot_general dimension numbers contracting the last (lane) axis of both
# operands: ``(M, N) x (K, N) -> (M, K)``, the MXU's native transposed-RHS
# form.
_CONTRACT_LANES = (((1,), (1,)), ((), ()))


def _pallas_call(name: str, kernel, **kw):
    """``pl.pallas_call`` launched under the name scopes ``kernel/<name>``.

    A profile then tells the kernel (scope ``.../kernel``) from the pads,
    transposes and slices its callers put around it; ``name``, the public
    kernel function's, stays the launch's op name in the profile."""
    call = pl.pallas_call(kernel, **kw)

    def launch(*operands):
        with jax.named_scope("kernel"), jax.named_scope(name):
            return call(*operands)

    return launch


def interpret_only(what: str, interpret: bool) -> None:
    """Refuse a compiled (TPU) launch of a kernel Mosaic cannot lower.

    The seeded gather round, the fused seeded encode and the schedule
    replay index VMEM values at arbitrary positions (``x[idx]``) and the
    replay also slices at unaligned dynamic offsets; the TPU compiler
    rejects both ("Only 2D gather is supported", no ``dynamic_slice``
    lowering).  They stay correct in interpret mode, so CPU runs keep
    them; on TPU the caller gets this error instead of a compiler trace.
    """
    if not interpret:
        raise NotImplementedError(
            f"{what} does not compile for TPU: Mosaic cannot lower its "
            "arbitrary-index in-kernel gathers.  It runs only in interpret "
            "mode (off-TPU); on TPU use the dense-tile / tiled kernels or "
            "the XLA replay executors.")


def _check_kernel(H_ref, vals_ref, erased_ref, sums_ref, cnt_ref, pos_ref,
                  coeff_ref):
    H = H_ref[...]  # (BP, N) f32
    e = erased_ref[...][:, 0]  # (N,) f32: 1.0 = erased
    Hb = (H != 0.0).astype(jnp.float32)

    cnt = jax.lax.dot(Hb, e[:, None], precision=_HIGH)  # (BP,1)
    known = vals_ref[...] * (1.0 - e)[:, None]  # (N, BV)
    sums = jax.lax.dot(H, known, precision=_HIGH)  # (BP,BV)

    # erased-neighbour index per row: max over iota masked to erased edges
    idx = jax.lax.broadcasted_iota(jnp.int32, H.shape, 1)
    mask = (Hb * e[None, :]) > 0.0
    pos = jnp.max(jnp.where(mask, idx, -1), axis=1)  # (BP,)
    onehot = (idx == pos[:, None]).astype(jnp.float32)
    coeff = jnp.sum(H * onehot, axis=1)  # (BP,)

    sums_ref[...] = sums
    cnt_ref[...] = cnt
    pos_ref[...] = pos[:, None]
    coeff_ref[...] = coeff[:, None]


@functools.partial(jax.jit, static_argnames=("bp", "bv", "interpret"))
def check_pass(H: jax.Array, values: jax.Array, erased_f: jax.Array, *,
               bp: int = 128, bv: int = 128, interpret: bool | None = None):
    """Inputs (already padded by ops.py): H (p, N) f32, values (N, V) f32,
    erased_f (N, 1) f32.  p % bp == 0, V % bv == 0, N % 128 == 0.

    ``interpret=None`` = backend-detected (compiled on TPU, else interpret).

    Returns (sums (p, V), cnt (p, 1), pos (p, 1) i32, coeff (p, 1))."""
    interpret = detect_interpret(interpret)
    p, N = H.shape
    V = values.shape[1]
    grid = (p // bp, V // bv)
    return _pallas_call(
        "check_pass",
        _check_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bp, N), lambda i, j: (i, 0)),   # H tile: reused over j
            pl.BlockSpec((N, bv), lambda i, j: (0, j)),   # payload tile
            pl.BlockSpec((N, 1), lambda i, j: (0, 0)),    # erasure mask
        ],
        out_specs=[
            pl.BlockSpec((bp, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bp, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, V), jnp.float32),
            jax.ShapeDtypeStruct((p, 1), jnp.float32),
            jax.ShapeDtypeStruct((p, 1), jnp.int32),
            jax.ShapeDtypeStruct((p, 1), jnp.float32),
        ],
        interpret=interpret,
    )(H, values, erased_f)


# ------------------------------------------------- shared flooding round --


def _check_tile_proposal(H, known, e):
    """One check tile's resolution proposal against the ROUND-START state.

    ``H (bp, N)`` is a tile of check rows; ``known (BV, N) = vals·(1-e)``
    and ``e (1, N)`` are the round-start known values / erasure mask, with
    the code axis on LANES (the payload rows sit on sublanes, so a scalar
    payload costs one 8-row tile, not an ``(N, 128)`` lane-padded column).
    Returns ``(resolved (1, N) ∈ {0, 1}, scattered (BV, N))``: which
    coordinates THIS tile resolves and the values it writes, with the
    lowest row in the tile winning intra-tile ties.  This is the ONE
    implementation of the flooding-round check/variable math — every fused
    kernel (resident or tiled, fixed or adaptive, batched or not) builds
    its round from it, so all variants follow the identical erasure
    trajectory (same solvability decisions, same resolved neighbour, same
    lowest-index-check tie-break).

    Counts, positions and coefficients are exact: ``cnt`` is a lane sum of
    0/1 flags, ``pos`` a lane max of column indices, and ``coeff`` a
    ``HIGHEST``-precision contraction of a one-nonzero row against ones
    (the multi-pass f32 matmul reconstructs a single f32 term exactly).
    Only the value sums carry f32 rounding.
    """
    Hb = H != 0.0
    col = jax.lax.broadcasted_iota(jnp.int32, H.shape, 1)  # (bp, N)
    row = jax.lax.broadcasted_iota(jnp.int32, H.shape, 0)  # (bp, N)
    emask = Hb & (e > 0.0)                                  # (bp, N)
    cnt = jnp.sum(emask.astype(jnp.float32), axis=1, keepdims=True)
    solvable = cnt == 1.0                                   # (bp, 1)
    pos = jnp.max(jnp.where(emask, col, -1), axis=1, keepdims=True)
    onehot = (col == pos) & solvable                        # (bp, N) bool
    # (BV, bp) contractions over the lane (code) axis of both operands
    sums = jax.lax.dot_general(known, H, _CONTRACT_LANES, precision=_HIGH)
    coeff = jax.lax.dot_general(jnp.ones_like(known),
                                jnp.where(onehot, H, 0.0), _CONTRACT_LANES,
                                precision=_HIGH)
    new_val = -sums / jnp.where(coeff == 0.0, 1.0, coeff)   # (BV, bp)
    # Several checks may resolve the same coordinate; keep the
    # lowest-index check's (consistent) value deterministically.
    winner_row = jnp.min(jnp.where(onehot, row, H.shape[0]), axis=0,
                         keepdims=True)                     # (1, N)
    winner = (onehot & (row == winner_row)).astype(jnp.float32)
    resolved = jnp.max(winner, axis=0, keepdims=True)       # (1, N)
    scattered = jax.lax.dot(new_val, winner, precision=_HIGH)  # (BV, N)
    return resolved, scattered


def _apply_round(vals, e, resolved, scattered):
    vals = jnp.where(resolved > 0.0, scattered, vals)
    e = jnp.where(resolved > 0.0, 0.0, e)
    return vals, e


def _resident_round(H):
    """Round function for a whole-H-in-VMEM tile (the resident kernels)."""
    def round_body(vals, e, t):
        del t                              # no streaming state to rotate
        known = vals * (1.0 - e)
        return _apply_round(vals, e, *_check_tile_proposal(H, known, e))

    return round_body


def _streamed_round(h_hbm, h_scratch, sem, *, bp: int):
    """Round function streaming H over check tiles from HBM.

    ``h_hbm`` is the full ``(p, N)`` ref left in HBM (``memory_space=ANY``,
    ``p % bp == 0``); ``h_scratch (2, bp, N)`` and ``sem (2,)`` are the
    double-buffered VMEM stream slots.  The pipeline runs on a GLOBAL tile
    counter ``g = round * n_tiles + j``: slot ``g % 2``, tile ``g %
    n_tiles``.  Tile ``g+1``'s DMA is started before waiting on tile ``g``
    — unconditionally, so during round ``t``'s LAST tile the prefetch
    lands on tile 0 of round ``t+1``: the double buffer never resets at a
    round boundary and the first tile of every round (after the first) is
    already in flight when the round starts.  Every tile's proposal is
    still computed against the round-start ``(vals, e)`` and merged
    first-tile-wins (tiles ascend the check axis, so the winner is the
    globally lowest check row — bit-identical to the resident merge).

    Returns ``(round_body(vals, e, t), prime, drain)``: callers start the
    pipeline with ``prime()`` before the decode loop and consume the one
    always-in-flight prefetch with ``drain(rounds_done)`` after it (the
    loop exits with tile 0 of round ``rounds_done`` outstanding — also
    true for 0 rounds, where the primed first DMA is the outstanding one).
    """
    n_tiles = h_hbm.shape[0] // bp

    def get_dma(g):
        return pltpu.make_async_copy(
            h_hbm.at[pl.ds((g % n_tiles) * bp, bp), :],
            h_scratch.at[g % 2], sem.at[g % 2])

    def prime():
        get_dma(0).start()

    def drain(rounds_done):
        get_dma(rounds_done * n_tiles).wait()

    def round_body(vals, e, t):
        known = vals * (1.0 - e)
        base = t * n_tiles

        def tile_step(j, carry):
            resolved, scattered = carry
            g = base + j
            get_dma(g + 1).start()         # j == n_tiles-1: next ROUND's tile 0
            get_dma(g).wait()
            t_res, t_scat = _check_tile_proposal(h_scratch[g % 2], known, e)
            take = (t_res > 0.0) & (resolved <= 0.0)
            return (jnp.maximum(resolved, t_res),
                    jnp.where(take, t_scat, scattered))

        resolved, scattered = jax.lax.fori_loop(
            0, n_tiles, tile_step, (jnp.zeros_like(e), jnp.zeros_like(vals)))
        return _apply_round(vals, e, resolved, scattered)

    return round_body, prime, drain


def _fixed_loop(round_body, vals, e, iters: int):
    """Exactly ``iters`` flooding rounds (the paper's fixed-D decode).
    The round index is passed through so streamed rounds can keep their
    cross-round DMA pipeline position."""
    return jax.lax.fori_loop(0, iters, lambda t, c: round_body(*c, t),
                             (vals, e))


def _adaptive_loop(round_body, vals, e, budget):
    """Early-exit rounds: stop when a round makes no progress, nothing is
    erased, or ``budget`` rounds have run (``budget`` may be traced — the
    per-slot round budgets of the batched-adaptive kernels never
    recompile).  Returns ``(vals, e, rounds_used)``."""
    def cond(carry):
        _, e_, d, progressed = carry
        return (d < budget) & progressed & (jnp.max(e_) > 0.0)

    def body(carry):
        vals_, e_, d, _ = carry
        vals2, e2 = round_body(vals_, e_, d)
        return vals2, e2, d + 1, jnp.any(e2 != e_)

    vals, e, d, _ = jax.lax.while_loop(
        cond, body, (vals, e, jnp.int32(0), jnp.bool_(True)))
    return vals, e, d


# ------------------------------------------------------------ fused decode --


def _decode_kernel(H_ref, vals_ref, erased_ref, out_vals_ref, out_erased_ref,
                   *, iters: int):
    round_body = _resident_round(H_ref[...])  # H resident across all rounds
    vals, e = _fixed_loop(round_body, vals_ref[...], erased_ref[...], iters)
    out_vals_ref[...] = vals
    out_erased_ref[...] = e


@functools.partial(jax.jit, static_argnames=("iters", "bv", "interpret"))
def decode_fused(H: jax.Array, values: jax.Array, erased_f: jax.Array, *,
                 iters: int, bv: int = 8, interpret: bool | None = None):
    """Whole fixed-``iters`` decode in one ``pallas_call``.

    Inputs (already padded by ops.py): H (p, N) f32 with p % 8 == 0 and
    N % 128 == 0; values (V, N) f32 with V % bv == 0; erased_f (1, N) f32.

    ``interpret=None`` = backend-detected (compiled on TPU, else interpret).

    Returns (values (V, N) f32, erased (1, N) f32) after ``iters`` rounds.
    """
    interpret = detect_interpret(interpret)
    p, N = H.shape
    V = values.shape[0]
    grid = (V // bv,)
    return _pallas_call(
        "decode_fused",
        functools.partial(_decode_kernel, iters=iters),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, N), lambda j: (0, 0)),  # H: resident, reused over j
            pl.BlockSpec((bv, N), lambda j: (j, 0)),  # payload slice
            pl.BlockSpec((1, N), lambda j: (0, 0)),   # initial erasure mask
        ],
        out_specs=[
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            # every grid step recomputes the identical erasure trajectory and
            # rewrites the same block — benign (sequential grid on TPU).
            pl.BlockSpec((1, N), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=interpret,
    )(H, values, erased_f)


# ---------------------------------------------------- symbol-major decode --


def _symbol_major_kernel(tgt_ref, nbr_ref, w_ref, scale_ref, cnt_ref,
                         vals_ref, out_ref, *, slots: int, chunk: int):
    """One ``(N, bv)`` lane tile: copy it, zero the erased rows, then
    resolve the scheduled rows in round order.

    Rows are addressed with dynamic sublane indices read from SMEM; the
    lane axis is walked in ``chunk``-wide strips so each row's running
    sum stays in registers."""
    n_erased, n_res = cnt_ref[0], cnt_ref[1]
    zero = jnp.zeros((1, chunk), jnp.float32)

    def strip(cols):
        out_ref[:, cols] = vals_ref[:, cols]

        def erase(i, c):
            out_ref[pl.ds(tgt_ref[i], 1), cols] = zero
            return c

        def resolve(i, c):
            b = i * slots
            acc = w_ref[b] * out_ref[pl.ds(nbr_ref[b], 1), cols]
            for s in range(1, slots):
                acc = acc + w_ref[b + s] * out_ref[pl.ds(nbr_ref[b + s], 1),
                                                   cols]
            out_ref[pl.ds(tgt_ref[i], 1), cols] = acc * scale_ref[i]
            return c

        jax.lax.fori_loop(0, n_erased, erase, 0)
        jax.lax.fori_loop(0, n_res, resolve, 0)

    @pl.loop(0, out_ref.shape[1] // chunk)
    def _(k):
        strip(pl.ds(pl.multiple_of(k * chunk, chunk), chunk))


@functools.partial(jax.jit, static_argnames=("bv", "chunk", "interpret"))
def decode_symbol_major(tgt: jax.Array, nbr: jax.Array, w: jax.Array,
                        scale: jax.Array, counts: jax.Array,
                        values: jax.Array, *, bv: int, chunk: int,
                        interpret: bool | None = None) -> jax.Array:
    """Apply a solved peel trajectory to a wide ``(N, V)`` payload.

    The payload stays as the workers produce it: code axis on sublanes,
    payload on lanes.  The schedule (built by ops.py from H and the mask
    alone) rides in SMEM as scalar prefetch:

    * ``tgt (N,) i32`` — the erased coordinates first, those resolved in
      round order ahead of those left unresolved, then the known ones;
    * ``counts (2,) i32`` — how many are erased, how many resolved;
    * ``nbr (N·slots,) i32`` / ``w (N·slots,) f32`` — for the ``i``-th
      resolved coordinate, the other neighbours of its winning check and
      their weights (padding slots point at the target itself with
      weight 0: that row is still zero when it is read);
    * ``scale (N,) f32`` — ``-1 / H[check, target]``.

    Each lane tile is copied, its erased rows zeroed, and each resolved
    row set to ``scale · Σ w·row`` in round order.  A coordinate resolved
    in round ``t`` reads only rows known at the start of ``t``, so the
    sequential updates equal the flooding rounds.  Erased rows the
    schedule leaves unresolved come back as 0, so the input's values on
    erased rows are never used.  ``bv`` is a multiple of ``chunk``, itself
    a multiple of 128 lanes; the last tile may be ragged.
    """
    interpret = detect_interpret(interpret)
    N, V = values.shape
    slots = nbr.shape[0] // N
    return _pallas_call(
        "decode_symbol_major",
        functools.partial(_symbol_major_kernel, slots=slots, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(V, bv),),
            in_specs=[pl.BlockSpec((N, bv), lambda j, *_: (0, j))],
            out_specs=pl.BlockSpec((N, bv), lambda j, *_: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, V), jnp.float32),
        interpret=interpret,
    )(tgt, nbr, w, scale, counts, values)


# --------------------------------------------------- edge-permuted decode --

_VREG_BITS = 10          # log2(8 sublanes x 128 lanes): slot bits in a vreg


def _stage_test(k: int):
    """The int32 mask that tests stage ``k``'s bit of a switch word."""
    return jnp.int32(np.array(1 << (k % 32), np.uint32).view(np.int32))


def _in_vreg_partner(bit: int):
    """For a stage on slot bit ``bit`` (< 10; slot ``row·128 + lane``):
    the ``(axis, size, stride)`` of its exchange and the ``(8, 128)``
    mask of the slots whose partner lies below them."""
    axis, size = (1, 128) if bit < 7 else (0, 8)
    s = 1 << (bit if bit < 7 else bit - 7)
    high = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), axis) & s) != 0
    return axis, size, s, high


def _exchange(x, partner, sw):
    """Where ``sw``, take the content of the slot's partner: two rolls and
    two selects, on lanes below bit 7 and on sublanes from it."""
    axis, size, s, high = partner
    above = pltpu.roll(x, size - s, axis)             # x at index + s
    below = pltpu.roll(x, s, axis)                    # x at index - s
    return jnp.where(sw, jnp.where(high, below, above), x)


# Vregs whose in-vreg stages one loop step interleaves, and how many
# consecutive vreg-level stages one pass applies to a group of vregs held
# in registers.
_BENES_UNROLL = 4
_BENES_GROUP = 3


def _benes(words_ref, planes, n_vregs: int, *, inverse: bool):
    """Apply the routed Beneš network (or, with the stages reversed, its
    inverse) in place to each ``(n_vregs, 8, 128)`` VMEM ref of
    ``planes``.  Its bits run high-low-high, so the stages on bits 10 and
    up (a vreg and its partner vreg, selects only) sit at both ends, run
    ``_BENES_GROUP`` at a time on groups of vregs in registers, and the
    19 on bits 0-9 form one block applied to each vreg in registers,
    ``_BENES_UNROLL`` vregs a loop step."""
    bits = stage_bits(n_vregs.bit_length() - 1 + _VREG_BITS)
    ks = list(range(len(bits)))[::-1 if inverse else 1]
    outer = [k for k in ks if bits[k] >= _VREG_BITS]
    inner = [k for k in ks if bits[k] < _VREG_BITS]

    def switch(word, k):
        return (word & _stage_test(k)) != 0

    def vreg_stages(run):
        """Consecutive stages on vreg bits ``lo .. lo + g - 1``: each loop
        step loads the ``2^g`` vregs that differ only there."""
        cs = [bits[k] - _VREG_BITS for k in run]
        lo, g = min(cs), len(run)

        def group(q, c):
            base = ((q >> lo) << (lo + g)) | (q & ((1 << lo) - 1))
            idx = [base + (i << lo) for i in range(1 << g)]
            words = {}

            def word(w, i):
                if (w, i) not in words:
                    words[w, i] = words_ref[w * n_vregs + idx[i]]
                return words[w, i]

            xs = [[ref[i] for i in idx] for ref in planes]
            for k, cbit in zip(run, cs):
                d = 1 << (cbit - lo)
                for i in range(1 << g):
                    if i & d:
                        continue
                    sw = switch(word(k // 32, i), k)
                    for x in xs:
                        x[i], x[i | d] = (jnp.where(sw, x[i | d], x[i]),
                                          jnp.where(sw, x[i], x[i | d]))
            for ref, x in zip(planes, xs):
                for i, v in enumerate(idx):
                    ref[v] = x[i]
            return c

        jax.lax.fori_loop(0, n_vregs >> g, group, 0)

    unroll = min(_BENES_UNROLL, n_vregs)

    def in_vreg(q, c):
        vs = [q * unroll + u for u in range(unroll)]
        words = [{w: words_ref[w * n_vregs + v]
                  for w in sorted({k // 32 for k in inner})} for v in vs]
        xs = [[ref[v] for ref in planes] for v in vs]
        for k in inner:
            partner = _in_vreg_partner(bits[k])
            for u in range(len(vs)):
                sw = switch(words[u][k // 32], k)
                xs[u] = [_exchange(x, partner, sw) for x in xs[u]]
        for v, x_v in zip(vs, xs):
            for ref, x in zip(planes, x_v):
                ref[v] = x
        return c

    half = len(outer) // 2
    for side in (outer[:half], None, outer[half:]):
        if side is None:
            jax.lax.fori_loop(0, n_vregs // unroll, in_vreg, 0)
            continue
        for i in range(0, len(side), _BENES_GROUP):
            vreg_stages(side[i:i + _BENES_GROUP])


def _edge_permuted_kernel(words_ref, coeff_ref, vals_ref, erased_ref,
                          out_vals_ref, out_erased_ref, xv, xf, *,
                          iters: int, geom):
    """One payload column's fixed-``iters`` decode; the value and erasure
    carry live in the output refs, the edge planes in ``xv`` / ``xf``."""
    nN, nP, nE = geom.var_vregs, geom.check_vregs, geom.slot_vregs
    out_vals_ref[...] = vals_ref[...]
    out_erased_ref[...] = erased_ref[...]
    xv[...] = jnp.zeros(xv.shape, jnp.float32)
    xf[...] = jnp.zeros(xf.shape, jnp.float32)
    zero = jnp.zeros((nP, 8, 128), jnp.float32)

    def round_(carry):
        t, _ = carry
        vals, e = out_vals_ref[...], out_erased_ref[...]
        for s in range(geom.l_max):          # variable-major edges
            xv[pl.ds(s * nN, nN)] = vals
            xf[pl.ds(s * nN, nN)] = e
        _benes(words_ref, (xv, xf), nE, inverse=False)
        cnt, acc, ecoef = zero, zero, zero   # per check, over its planes
        for s in range(geom.r_max):
            sl = pl.ds(s * nP, nP)
            w, f = coeff_ref[sl], xf[sl] != 0.0
            edge = w != 0.0
            cnt = cnt + jnp.where(edge & f, 1.0, 0.0)
            acc = acc + jnp.where(edge & ~f, w * xv[sl], 0.0)
            ecoef = ecoef + jnp.where(edge & f, w, 0.0)
        new = -acc / jnp.where(ecoef == 0.0, 1.0, ecoef)
        solvable = cnt == 1.0
        for s in range(geom.r_max):          # propose on the erased edge
            sl = pl.ds(s * nP, nP)
            xf[sl] = jnp.where(solvable & (coeff_ref[sl] != 0.0)
                               & (xf[sl] != 0.0), 1.0, 0.0)
            xv[sl] = new
        if nE > geom.r_max * nP:
            tail = nE - geom.r_max * nP
            xf[pl.ds(geom.r_max * nP, tail)] = jnp.zeros((tail, 8, 128),
                                                         jnp.float32)
        _benes(words_ref, (xv, xf), nE, inverse=True)
        resolved = jnp.zeros(vals.shape, jnp.bool_)
        for s in range(geom.l_max):          # lowest resolving check wins
            sl = pl.ds(s * nN, nN)
            f = xf[sl] != 0.0
            vals = jnp.where(f & ~resolved, xv[sl], vals)
            resolved = resolved | f
        e = jnp.where(resolved, 0.0, e)
        out_vals_ref[...] = vals
        out_erased_ref[...] = e
        return t + 1, _progress(resolved, e)

    # A round that resolves nothing leaves every later round nothing to
    # do, so stopping there gives the fixed-D result exactly.
    jax.lax.while_loop(lambda c: (c[0] < iters) & c[1], round_,
                       (jnp.int32(0), jnp.max(erased_ref[...]) > 0.0))


def _progress(resolved, e):
    """Whether another round can resolve anything: this one resolved some
    coordinate, and some coordinate is still erased."""
    return ((jnp.max(jnp.where(resolved, 1.0, 0.0)) > 0.0)
            & (jnp.max(e) > 0.0))


@functools.partial(jax.jit, static_argnames=("iters", "geom", "interpret"))
def decode_edge_permuted(words: jax.Array, coeff: jax.Array,
                         values: jax.Array, erased_f: jax.Array, *,
                         iters: int, geom, interpret: bool | None = None):
    """Fixed-``iters`` flooding decode on the code's edges, no H read.

    ``geom`` is the :class:`repro.core.benes.EdgeGeometry` of the edge
    network; every operand is whole ``(8, 128)`` vregs, slot-major:

    * ``words (n_words·slot_vregs, 8, 128) i32`` — the routed switch
      words of the variable-major → check-major edge permutation;
    * ``coeff (r_max·check_vregs, 8, 128) f32`` — the check-major edge
      weights, 0 where a slot is no edge;
    * ``values (V·var_vregs, 8, 128) f32`` — V payload columns, each the
      code axis padded to ``var_vregs`` vregs (the grid walks them);
    * ``erased_f (var_vregs, 8, 128) f32`` — 1.0 where erased.

    Each round broadcasts values and flags onto the variable-major edges,
    permutes both to check-major, counts each check's erased edges and
    sums its known weighted values over its ``r_max`` planes, proposes
    ``-sum / coeff`` on the one erased edge of each check with a count of
    1, permutes back, and gives each erased variable its lowest resolving
    check's proposal: :func:`repro.core.decoder.peel_round`'s trajectory,
    the lowest-check tie-break of the other fused kernels.  Returns
    ``(values, erased_f)`` after ``iters`` rounds; unresolved coordinates
    keep their input values.  Rounds stop early once one resolves
    nothing or nothing is erased: the rounds left would change nothing.
    """
    interpret = detect_interpret(interpret)
    nN = geom.var_vregs
    col = pl.BlockSpec((nN, 8, 128), lambda c: (c, 0, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda c: (0, 0, 0))  # noqa: E731
    return _pallas_call(
        "decode_edge_permuted",
        functools.partial(_edge_permuted_kernel, iters=iters, geom=geom),
        grid=(values.shape[0] // nN,),
        in_specs=[whole(words), whole(coeff), col, whole(erased_f)],
        out_specs=[col, whole(erased_f)],
        out_shape=[jax.ShapeDtypeStruct(values.shape, jnp.float32),
                   jax.ShapeDtypeStruct(erased_f.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((geom.slot_vregs, 8, 128), jnp.float32)
                        for _ in range(2)],
        interpret=interpret,
    )(words, coeff, values, erased_f)


# --------------------------------------------------- batched fused decode --


def _decode_batch_kernel(H_ref, vals_ref, erased_ref, out_vals_ref,
                         out_erased_ref, *, iters: int):
    round_body = _resident_round(H_ref[...])  # H shared across the whole batch
    vals, e = _fixed_loop(round_body, vals_ref[0], erased_ref[0], iters)
    out_vals_ref[0] = vals
    out_erased_ref[0] = e


@functools.partial(jax.jit, static_argnames=("iters", "bv", "interpret"))
def decode_fused_batch(H: jax.Array, values: jax.Array, erased_f: jax.Array,
                       *, iters: int, bv: int = 8,
                       interpret: bool | None = None):
    """``B`` independent erasure patterns, one ``pallas_call``.

    Inputs (already padded by ops.py): H (p, N) f32 with p % 8 == 0 and
    N % 128 == 0; values (B, V, N) f32 with V % bv == 0; erased_f (B, 1, N)
    f32.  The grid is ``(B, V // bv)``; the H block's index map is constant,
    so H is fetched into VMEM once and stays resident while each query's
    payload/mask tiles stream through — the per-query marginal cost is the
    decode arithmetic alone, not a kernel launch + H reload.

    ``interpret=None`` = backend-detected (compiled on TPU, else interpret).

    Returns (values (B, V, N) f32, erased (B, 1, N) f32).
    """
    interpret = detect_interpret(interpret)
    p, N = H.shape
    B, V, _ = values.shape
    grid = (B, V // bv)
    return _pallas_call(
        "decode_fused_batch",
        functools.partial(_decode_batch_kernel, iters=iters),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, N), lambda b, j: (0, 0)),      # H: resident
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            # grid steps sharing a batch index recompute the identical
            # trajectory and rewrite the same block — benign (sequential
            # grid on TPU).
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, V, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, N), jnp.float32),
        ],
        interpret=interpret,
    )(H, values, erased_f)


# -------------------------------------------------- adaptive fused decode --


def _decode_adaptive_kernel(H_ref, vals_ref, erased_ref, out_vals_ref,
                            out_erased_ref, out_rounds_ref, *, max_iters: int):
    round_body = _resident_round(H_ref[...])
    vals, e, d = _adaptive_loop(round_body, vals_ref[...], erased_ref[...],
                                max_iters)
    out_vals_ref[...] = vals
    out_erased_ref[...] = e
    out_rounds_ref[...] = jnp.full((1, 1), d, jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_iters", "bv", "interpret"))
def decode_fused_adaptive(H: jax.Array, values: jax.Array,
                          erased_f: jax.Array, *, max_iters: int,
                          bv: int = 8, interpret: bool | None = None):
    """Early-exit decode in one launch: in-kernel ``while_loop`` that stops
    as soon as a round makes no progress (or nothing is erased), exactly the
    ``peel_decode_adaptive`` stopping rule — "decoding effort tracks the
    number of stragglers" without leaving the kernel.

    Inputs (already padded by ops.py) as for :func:`decode_fused`.  Returns
    (values (V, N) f32, erased (1, N) f32, rounds (1, 1) i32).  The erasure
    trajectory depends only on H and the initial mask, so every payload
    slice exits after the identical round count and the shared rounds output
    is written consistently by each grid step.
    """
    interpret = detect_interpret(interpret)
    p, N = H.shape
    V = values.shape[0]
    grid = (V // bv,)
    return _pallas_call(
        "decode_fused_adaptive",
        functools.partial(_decode_adaptive_kernel, max_iters=max_iters),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, N), lambda j: (0, 0)),  # H: resident
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(H, values, erased_f)


# ------------------------------------- per-slot adaptive batched decode --


def _decode_batch_adaptive_kernel(H_ref, vals_ref, erased_ref, budget_ref,
                                  out_vals_ref, out_erased_ref,
                                  out_rounds_ref):
    round_body = _resident_round(H_ref[...])  # H shared across the whole batch
    vals, e, d = _adaptive_loop(round_body, vals_ref[0], erased_ref[0],
                                budget_ref[0, 0, 0])  # this slot's budget
    out_vals_ref[0] = vals
    out_erased_ref[0] = e
    out_rounds_ref[...] = jnp.full((1, 1, 1), d, jnp.int32)


@functools.partial(jax.jit, static_argnames=("bv", "interpret"))
def decode_fused_batch_adaptive(H: jax.Array, values: jax.Array,
                                erased_f: jax.Array, budgets: jax.Array, *,
                                bv: int = 8, interpret: bool | None = None):
    """Per-slot adaptive decode of ``B`` independent patterns, ONE launch.

    Inputs (already padded by ops.py): H (p, N) f32 with p % 8 == 0 and
    N % 128 == 0; values (B, V, N) f32 with V % bv == 0; erased_f (B, 1, N)
    f32; budgets (B, 1) int32 — each slot's round budget.  The grid is
    ``(B, V // bv)`` with the H block's index map constant, so H is fetched
    into VMEM once and stays resident across the whole batch while per-slot
    payload/mask/budget tiles stream through.  Each grid step runs its own
    ``while_loop`` with the slot's convergence predicate (progress made AND
    erasures remain AND slot budget left) — converged slots exit after the
    exact round count ``peel_decode_adaptive`` would use, independent of the
    other slots.  The round budget is a TRACED operand, so serving layers
    can vary per-slot budgets launch-to-launch without recompiling.

    ``interpret=None`` = backend-detected (compiled on TPU, else interpret).

    Returns (values (B, V, N) f32, erased (B, 1, N) f32, rounds (B, 1) i32).
    """
    interpret = detect_interpret(interpret)
    p, N = H.shape
    B, V, _ = values.shape
    grid = (B, V // bv)
    vals, erased, rounds = _pallas_call(
        "decode_fused_batch_adaptive",
        _decode_batch_adaptive_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, N), lambda b, j: (0, 0)),      # H: resident
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),      # slot budget
        ],
        out_specs=[
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            # grid steps sharing a batch index recompute the identical
            # trajectory (it depends only on H, the mask, and the budget)
            # and rewrite the same block — benign (sequential grid on TPU).
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, V, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(H, values, erased_f, budgets[:, :, None])
    return vals, erased, rounds[:, :, 0]


# ---------------------------------------------- check-axis-tiled decodes --
#
# Same contracts as the resident kernels, with H left in HBM (p % bp == 0
# enforced by ops.py) and streamed through the double-buffered scratch.
# One scratch/semaphore signature shared by all four.


def _tiled_scratch(bp: int, N: int):
    return [pltpu.VMEM((2, bp, N), jnp.float32),
            pltpu.SemaphoreType.DMA((2,))]


def _check_tiled_operands(p: int, N: int, V: int, bp: int, bv: int) -> None:
    """The tile loops FLOOR-divide (``p // bp``, ``V // bv``), so unpadded
    operands would silently drop trailing check rows / payload columns —
    fail loudly instead (the ops.py wrappers pad before calling)."""
    if p % bp or N % 128 or V % bv:
        raise ValueError(
            "tiled decode operands must be pre-padded (ops.py wrappers do "
            f"this): need p % bp == 0, N % 128 == 0, V % bv == 0; got "
            f"p={p} bp={bp}, N={N}, V={V} bv={bv}")


def _decode_tiled_kernel(H_hbm, vals_ref, erased_ref, out_vals_ref,
                         out_erased_ref, h_scratch, sem, *, iters: int,
                         bp: int):
    round_body, prime, drain = _streamed_round(H_hbm, h_scratch, sem, bp=bp)
    prime()
    vals, e = _fixed_loop(round_body, vals_ref[...], erased_ref[...], iters)
    drain(jnp.int32(iters))
    out_vals_ref[...] = vals
    out_erased_ref[...] = e


@functools.partial(jax.jit, static_argnames=("iters", "bp", "bv", "interpret"))
def decode_fused_tiled(H: jax.Array, values: jax.Array, erased_f: jax.Array,
                       *, iters: int, bp: int = 128, bv: int = 8,
                       interpret: bool | None = None):
    """Fixed-``iters`` decode with H STREAMED over check tiles.

    Inputs (already padded by ops.py): H (p, N) f32 with p % bp == 0 and
    N % 128 == 0; values (V, N) f32 with V % bv == 0; erased_f (1, N) f32.
    Same trajectory and output contract as :func:`decode_fused`; the VMEM
    working set is ``2·bp·N`` stream slots + the ``(bv, N)`` carry instead
    of the whole ``(p, N)`` H — this is the variant ``backend="auto"``
    routes to when ``core/decoder.vmem_bytes_estimate`` says the resident
    kernel will not fit.
    """
    interpret = detect_interpret(interpret)
    p, N = H.shape
    V = values.shape[0]
    _check_tiled_operands(p, N, V, bp, bv)
    grid = (V // bv,)
    return _pallas_call(
        "decode_fused_tiled",
        functools.partial(_decode_tiled_kernel, iters=iters, bp=bp),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # H: stays in HBM
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        scratch_shapes=_tiled_scratch(bp, N),
        interpret=interpret,
    )(H, values, erased_f)


def _decode_batch_tiled_kernel(H_hbm, vals_ref, erased_ref, out_vals_ref,
                               out_erased_ref, h_scratch, sem, *, iters: int,
                               bp: int):
    round_body, prime, drain = _streamed_round(H_hbm, h_scratch, sem, bp=bp)
    prime()
    vals, e = _fixed_loop(round_body, vals_ref[0], erased_ref[0], iters)
    drain(jnp.int32(iters))
    out_vals_ref[0] = vals
    out_erased_ref[0] = e


@functools.partial(jax.jit, static_argnames=("iters", "bp", "bv", "interpret"))
def decode_fused_batch_tiled(H: jax.Array, values: jax.Array,
                             erased_f: jax.Array, *, iters: int,
                             bp: int = 128, bv: int = 8,
                             interpret: bool | None = None):
    """``B`` independent patterns with H streamed over check tiles.

    Same contract as :func:`decode_fused_batch` (values (B, V, N), erased_f
    (B, 1, N), both padded); the grid runs over ``(B, V // bv)`` and every
    grid step re-streams the H tiles from HBM while its slot's payload/mask
    tiles live in VMEM.  (On the batch axis the resident kernel amortizes
    the H fetch across slots; the tiled kernel instead bounds VMEM by
    ``2·bp·N`` — the trade recorded in the README matrix.)
    """
    interpret = detect_interpret(interpret)
    p, N = H.shape
    B, V, _ = values.shape
    _check_tiled_operands(p, N, V, bp, bv)
    grid = (B, V // bv)
    return _pallas_call(
        "decode_fused_batch_tiled",
        functools.partial(_decode_batch_tiled_kernel, iters=iters, bp=bp),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # H: stays in HBM
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, V, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, N), jnp.float32),
        ],
        scratch_shapes=_tiled_scratch(bp, N),
        interpret=interpret,
    )(H, values, erased_f)


def _decode_adaptive_tiled_kernel(H_hbm, vals_ref, erased_ref, out_vals_ref,
                                  out_erased_ref, out_rounds_ref, h_scratch,
                                  sem, *, max_iters: int, bp: int):
    round_body, prime, drain = _streamed_round(H_hbm, h_scratch, sem, bp=bp)
    prime()
    vals, e, d = _adaptive_loop(round_body, vals_ref[...], erased_ref[...],
                                max_iters)
    drain(d)
    out_vals_ref[...] = vals
    out_erased_ref[...] = e
    out_rounds_ref[...] = jnp.full((1, 1), d, jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("max_iters", "bp", "bv", "interpret"))
def decode_fused_adaptive_tiled(H: jax.Array, values: jax.Array,
                                erased_f: jax.Array, *, max_iters: int,
                                bp: int = 128, bv: int = 8,
                                interpret: bool | None = None):
    """Early-exit decode with H streamed over check tiles.

    Same stopping rule, trajectory, and output contract as
    :func:`decode_fused_adaptive` (values (V, N), erased (1, N),
    rounds (1, 1)); the in-kernel ``while_loop`` wraps the streamed round,
    so an early exit also stops the H streaming — decode bandwidth tracks
    the realized straggler load, not the worst case.
    """
    interpret = detect_interpret(interpret)
    p, N = H.shape
    V = values.shape[0]
    _check_tiled_operands(p, N, V, bp, bv)
    grid = (V // bv,)
    return _pallas_call(
        "decode_fused_adaptive_tiled",
        functools.partial(_decode_adaptive_tiled_kernel, max_iters=max_iters,
                          bp=bp),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # H: stays in HBM
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=_tiled_scratch(bp, N),
        interpret=interpret,
    )(H, values, erased_f)


def _decode_batch_adaptive_tiled_kernel(H_hbm, vals_ref, erased_ref,
                                        budget_ref, out_vals_ref,
                                        out_erased_ref, out_rounds_ref,
                                        h_scratch, sem, *, bp: int):
    round_body, prime, drain = _streamed_round(H_hbm, h_scratch, sem, bp=bp)
    prime()
    vals, e, d = _adaptive_loop(round_body, vals_ref[0], erased_ref[0],
                                budget_ref[0, 0, 0])  # this slot's budget
    drain(d)
    out_vals_ref[0] = vals
    out_erased_ref[0] = e
    out_rounds_ref[...] = jnp.full((1, 1, 1), d, jnp.int32)


@functools.partial(jax.jit, static_argnames=("bp", "bv", "interpret"))
def decode_fused_batch_adaptive_tiled(H: jax.Array, values: jax.Array,
                                      erased_f: jax.Array,
                                      budgets: jax.Array, *, bp: int = 128,
                                      bv: int = 8,
                                      interpret: bool | None = None):
    """Per-slot adaptive decode of ``B`` patterns with H streamed per slot.

    Same contract as :func:`decode_fused_batch_adaptive` (budgets (B, 1)
    int32 stays a TRACED operand — varying per-slot budgets never
    recompile); each grid step runs its own streamed ``while_loop``, so a
    light slot stops both its compute AND its H streaming after 1-2 rounds.
    """
    interpret = detect_interpret(interpret)
    p, N = H.shape
    B, V, _ = values.shape
    _check_tiled_operands(p, N, V, bp, bv)
    grid = (B, V // bv)
    vals, erased, rounds = _pallas_call(
        "decode_fused_batch_adaptive_tiled",
        functools.partial(_decode_batch_adaptive_tiled_kernel, bp=bp),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # H: stays in HBM
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),      # slot budget
        ],
        out_specs=[
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, V, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        scratch_shapes=_tiled_scratch(bp, N),
        interpret=interpret,
    )(H, values, erased_f, budgets[:, :, None])
    return vals, erased, rounds[:, :, 0]


# --------------------------------------------------- seeded tiled decodes --
#
# The same four contracts with the DMA'd H scratch replaced by in-register
# tile GENERATION: no H operand, no stream slots, no semaphores — the only
# HBM traffic is the (bv, N) payload carry and masks.  The structure spec
# (repro.core.ldpc.SeededStructure — plain ints/tuples, hashable) is a
# STATIC argument, so the per-layer affine constants are compiled into the
# kernel and tile regeneration is pure VPU arithmetic on iotas.


def _mix32_jnp(x):
    """jnp twin of ``repro.core.ldpc._mix32`` (lowbias32 avalanche).

    uint32 in, uint32 out; multiplication wraps mod 2^32 and ``>>`` on an
    unsigned dtype is a logical shift, so every intermediate matches the
    NumPy reference bit for bit.
    """
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _seeded_row_params(spec, rows):
    """Per-row layer constants of global check rows ``rows`` (any shape).

    Returns ``(t, a, b, jl)``: layer index, affine stride/offset (selected
    by a static unroll over the — small — layer count, so ``spec`` stays
    compiled-in), and the within-layer row.  Rows outside ``[0,
    spec.rows)`` get ``a == b == 0`` (no layer matches), so their column
    draws land on 0 and callers mask them with a ``rows < spec.rows``
    validity test, exactly like the dense generator's zero rows.
    """
    t = rows // spec.rows_per_layer
    a = jnp.zeros(rows.shape, jnp.int32)
    b = jnp.zeros(rows.shape, jnp.int32)
    for tt in range(spec.layers):          # static unroll: layers == l (small)
        a = jnp.where(t == tt, jnp.int32(spec.strides[tt]), a)
        b = jnp.where(t == tt, jnp.int32(spec.offsets[tt]), b)
    jl = rows - t * spec.rows_per_layer
    return t, a, b, jl


def _seeded_edge_weight(spec, rows, s: int):
    """Edge weight of slot ``s`` on global check rows ``rows`` — the
    uint32-hash-to-exact-f32 map shared bit-for-bit with the NumPy
    reference (``repro.core.ldpc._structure_rows_raw``)."""
    edge = (rows * spec.row_weight + s).astype(jnp.uint32)
    u = _mix32_jnp(edge ^ jnp.uint32(spec.wseed))
    sign = 1.0 - 2.0 * (u & 1).astype(jnp.int32).astype(jnp.float32)
    m = (u >> 9).astype(jnp.int32).astype(jnp.float32)   # [0, 2^23)
    return sign * (1.0 + m * jnp.float32(2.0 ** -23))    # exact f32


def seeded_h_tile(spec, row0, bp: int, n_pad: int):
    """Regenerate the dense ``(bp, n_pad)`` H tile at check row ``row0``.

    Pure jnp — usable inside a Pallas kernel body or as a plain traced
    function (the bit-exactness tests call it directly).  Bit-exact against
    ``repro.core.ldpc.seeded_h_rows(spec, row0, row0 + bp)`` padded with
    zero columns to ``n_pad``: column draws are int32 affine arithmetic
    (``spec`` bounds the stride so ``a*x + b`` never overflows), edge
    weights are uint32 hash bits mapped through exact f32 steps.  Rows past
    ``spec.rows`` (check-axis padding) come out all-zero — never solvable,
    exactly like the zero-padded rows the materialized wrappers append.

    ``row0`` may be traced (the tile loop's ``j * bp``); ``bp``/``n_pad``
    are static.
    """
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bp, 1), 0)  # global
    _, a, b, jl = _seeded_row_params(spec, rows)
    valid = (rows < spec.rows).astype(jnp.float32)      # (bp, 1) row mask
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (bp, n_pad), 1)
    H = jnp.zeros((bp, n_pad), jnp.float32)
    for s in range(spec.row_weight):       # static unroll: r compares + FMAs
        x = jl * spec.row_weight + s
        col = (a * x + b) % spec.cols      # int32-safe by the stride bound
        w = _seeded_edge_weight(spec, rows, s)
        H = H + (col_iota == col).astype(jnp.float32) * (w * valid)
    return H


def _seeded_round(spec, *, bp: int, p_pad: int, n_pad: int):
    """Round function regenerating H tiles from the seed (no DMA at all).

    Mirrors :func:`_streamed_round`'s tile loop and first-tile-wins merge
    exactly — tiles ascend the check axis against the round-start state —
    so the seeded trajectory is bit-identical to the streamed/resident
    ones on the same code; the only difference is where the tile's floats
    come from.
    """
    n_tiles = p_pad // bp

    def round_body(vals, e, t):
        del t                              # no pipeline position to keep
        known = vals * (1.0 - e)

        def tile_step(j, carry):
            resolved, scattered = carry
            H_tile = seeded_h_tile(spec, j * bp, bp, n_pad)
            t_res, t_scat = _check_tile_proposal(H_tile, known, e)
            take = (t_res > 0.0) & (resolved <= 0.0)
            return (jnp.maximum(resolved, t_res),
                    jnp.where(take, t_scat, scattered))

        resolved, scattered = jax.lax.fori_loop(
            0, n_tiles, tile_step, (jnp.zeros_like(e), jnp.zeros_like(vals)))
        return _apply_round(vals, e, resolved, scattered)

    return round_body


def _mod_mul(m, mult: int, c: int):
    """``(mult * m) % c`` for traced int32 ``m`` in ``[0, c)`` with STATIC
    Python ints ``mult``/``c``, never overflowing int32.

    When the direct product fits, use it.  Otherwise split ``m = hi·2^k +
    lo`` and fold ``2^k`` into the multiplier on the host: each partial
    product is reduced mod ``c`` before the final add, so every
    intermediate stays under ``2^31``.  A ``k`` exists whenever
    ``c^3 < 2^62`` (far beyond any supported code length); otherwise the
    caller's code is too large for int32 index arithmetic and we say so.
    """
    mult %= c
    if mult * (c - 1) < 2**31:
        return (m * jnp.int32(mult)) % jnp.int32(c)
    for k in range(1, 31):
        if ((c - 1) * ((1 << k) - 1) < 2**31
                and (c - 1) * ((c - 1) >> k) < 2**31):
            mult_k = (mult << k) % c
            hi = m >> k
            lo = m & ((1 << k) - 1)
            t1 = (hi * jnp.int32(mult_k)) % jnp.int32(c)
            t2 = (lo * jnp.int32(mult)) % jnp.int32(c)
            return (t1 + t2) % jnp.int32(c)
    raise ValueError(
        f"cols={c} too large for int32 modular inverse arithmetic "
        f"(needs c^3 < 2^62); use seeded_mode='dense_tile'")


def _seeded_gather_round(spec, *, bp: int, p_pad: int, n_pad: int):
    """Edge-proportional round: gathers + segment-sums, NO dense tile.

    Check pass: for each check row in the tile, regenerate only its ``r``
    (column, weight) draws and accumulate cnt/pos/coeff/sums with ``r``
    payload gathers — cnt is an exact small-integer f32 sum, pos an int32
    sum that collapses to the single erased neighbour exactly when the row
    is solvable, coeff the single surviving weight (bit-equal to the dense
    tile's masked row-sum).  Variable pass: instead of a one-hot scatter
    matmul, invert the layered affine permutations (per-layer modular
    inverse, a compile-time Python ``pow``) so each column computes its one
    candidate check row per layer and gathers that row's proposal;
    candidates ascend in row index with the layer, so first-match-wins IS
    the lowest-row tie-break, and the cross-tile merge below is the same
    first-tile-wins carry as :func:`_seeded_round` — the erasure
    trajectory is bit-identical to the dense-tile mode.  Values agree to
    f32 summation order only (draw-order r-term sums here vs tile-dot
    reductions there).
    """
    n_tiles = p_pad // bp
    r = spec.row_weight
    # Modular inverses of the layer strides (exist: gcd(a_t, cols) == 1 by
    # construction) — Python ints, compiled into the kernel.
    inv = [pow(spec.strides[tt], -1, spec.cols) for tt in range(spec.layers)]

    def round_body(vals, e, t_round):
        del t_round                        # no pipeline position to keep
        # the check and variable passes below run column-major ((rows, 1)
        # per-check / (n_pad, 1) per-column vectors); the carry is
        # lane-major like every other kernel's, transposed at the edges
        known = (vals * (1.0 - e)).T                          # (n_pad, BV)
        e_flat = e[0]                                         # (n_pad,)
        col2 = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 1), 0)

        def tile_step(j, carry):
            resolved, scattered = carry
            rows = j * bp + jax.lax.broadcasted_iota(jnp.int32, (bp, 1), 0)
            _, a, b, jl = _seeded_row_params(spec, rows)
            valid = rows < spec.rows                          # (bp, 1)
            cnt = jnp.zeros((bp, 1), jnp.float32)
            pos = jnp.zeros((bp, 1), jnp.int32)
            coeff = jnp.zeros((bp, 1), jnp.float32)
            sums = jnp.zeros((bp, known.shape[1]), jnp.float32)
            for s in range(r):             # static unroll: r gathers
                col_s = (a * (jl * r + s) + b) % spec.cols    # (bp, 1)
                w_s = (_seeded_edge_weight(spec, rows, s)
                       * valid.astype(jnp.float32))           # H entry
                eg = e_flat[col_s]                            # (bp, 1)
                cnt = cnt + eg             # exact: r << 2^24
                pos = pos + col_s * eg.astype(jnp.int32)
                coeff = coeff + w_s * eg
                sums = sums + w_s * known[col_s[:, 0]]        # (bp, BV)
            solvable = (cnt == 1.0) & valid
            new_val = -sums / jnp.where(coeff == 0.0, 1.0, coeff)
            pos = jnp.where(solvable, pos, jnp.int32(-1))
            solvable_f = solvable.astype(jnp.float32)[:, 0]   # (bp,)
            pos_flat = pos[:, 0]

            # Variable pass: each column's candidate row in layer tt is
            # row tt·rpl + x//r with x = a_tt^{-1}·(col - b_tt) mod cols.
            t_res = jnp.zeros((n_pad, 1), jnp.float32)
            t_scat = jnp.zeros((n_pad, known.shape[1]), jnp.float32)
            for tt in range(spec.layers):  # static unroll, rows ascend in tt
                mm = (col2 - spec.offsets[tt]) % spec.cols
                x = _mod_mul(mm, inv[tt], spec.cols)
                row_g = tt * spec.rows_per_layer + x // r
                in_tile = row_g - j * bp
                idx = jnp.clip(in_tile, 0, bp - 1)            # (n_pad, 1)
                ok = (in_tile >= 0) & (in_tile < bp) & (col2 < spec.cols)
                sg = solvable_f[idx]
                pg = pos_flat[idx]
                nv = new_val[idx[:, 0]]                       # (n_pad, BV)
                hit = ok & (sg > 0.0) & (pg == col2)
                take = hit & (t_res <= 0.0)
                t_res = jnp.where(take, 1.0, t_res)
                t_scat = jnp.where(take, nv, t_scat)

            t_res, t_scat = t_res.T, t_scat.T                 # lane-major
            take = (t_res > 0.0) & (resolved <= 0.0)
            return (jnp.maximum(resolved, t_res),
                    jnp.where(take, t_scat, scattered))

        resolved, scattered = jax.lax.fori_loop(
            0, n_tiles, tile_step, (jnp.zeros_like(e), jnp.zeros_like(vals)))
        return _apply_round(vals, e, resolved, scattered)

    return round_body


def _seeded_round_for(spec, mode: str, *, bp: int, p_pad: int, n_pad: int):
    """Round-body factory behind the static ``mode`` knob of the seeded
    kernels: ``"dense_tile"`` regenerates + matmuls, ``"gather"`` runs the
    edge-proportional round.  Identical erasure trajectories."""
    if mode == "dense_tile":
        return _seeded_round(spec, bp=bp, p_pad=p_pad, n_pad=n_pad)
    if mode == "gather":
        return _seeded_gather_round(spec, bp=bp, p_pad=p_pad, n_pad=n_pad)
    raise ValueError(f"seeded mode must be one of {SEEDED_MODES}, "
                     f"got {mode!r}")


def _check_seeded_operands(spec, N: int, V: int, bp: int, bv: int) -> None:
    if N % 128 or V % bv or N < spec.cols or bp % 8:
        raise ValueError(
            "seeded decode operands must be pre-padded (ops.py wrappers do "
            f"this): need N % 128 == 0, V % bv == 0, N >= spec.cols, "
            f"bp % 8 == 0; got N={N} (cols={spec.cols}), V={V} bv={bv}, "
            f"bp={bp}")


def _seeded_p_pad(spec, bp: int) -> int:
    """Check-axis extent of the tile loop: spec.rows rounded up to bp."""
    return spec.rows + (-spec.rows) % bp


def _decode_seeded_kernel(vals_ref, erased_ref, out_vals_ref, out_erased_ref,
                          *, spec, iters: int, bp: int, mode: str):
    N = vals_ref.shape[1]
    round_body = _seeded_round_for(spec, mode, bp=bp,
                                   p_pad=_seeded_p_pad(spec, bp), n_pad=N)
    vals, e = _fixed_loop(round_body, vals_ref[...], erased_ref[...], iters)
    out_vals_ref[...] = vals
    out_erased_ref[...] = e


@functools.partial(jax.jit,
                   static_argnames=("spec", "iters", "bp", "bv", "interpret",
                                    "mode"))
def decode_seeded(spec, values: jax.Array, erased_f: jax.Array, *,
                  iters: int, bp: int = 128, bv: int = 8,
                  interpret: bool | None = None, mode: str = "dense_tile"):
    """Fixed-``iters`` decode with H REGENERATED from the seed per tile.

    Inputs (already padded by ops.py): values (V, N) f32 with N % 128 == 0
    covering ``spec.cols`` (padded columns are all-zero in the generated
    tiles, so they never move), erased_f (1, N) f32.  ``spec`` is the
    static :class:`repro.core.ldpc.SeededStructure`.  Same trajectory and
    output contract as :func:`decode_fused` / :func:`decode_fused_tiled`
    on the materialized H of the same code; the VMEM working set is ONE
    generated ``(bp, N)`` tile plus the ``(bv, N)`` carry, and H
    contributes ZERO bytes of operand traffic.
    """
    interpret = detect_interpret(interpret)
    if mode == "gather":
        interpret_only("seeded_mode='gather'", interpret)
    V, N = values.shape
    _check_seeded_operands(spec, N, V, bp, bv)
    grid = (V // bv,)
    return _pallas_call(
        "decode_seeded",
        functools.partial(_decode_seeded_kernel, spec=spec, iters=iters,
                          bp=bp, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=interpret,
    )(values, erased_f)


def _decode_seeded_batch_kernel(vals_ref, erased_ref, out_vals_ref,
                                out_erased_ref, *, spec, iters: int, bp: int,
                                mode: str):
    N = vals_ref.shape[2]
    round_body = _seeded_round_for(spec, mode, bp=bp,
                                   p_pad=_seeded_p_pad(spec, bp), n_pad=N)
    vals, e = _fixed_loop(round_body, vals_ref[0], erased_ref[0], iters)
    out_vals_ref[0] = vals
    out_erased_ref[0] = e


@functools.partial(jax.jit,
                   static_argnames=("spec", "iters", "bp", "bv", "interpret",
                                    "mode"))
def decode_seeded_batch(spec, values: jax.Array, erased_f: jax.Array, *,
                        iters: int, bp: int = 128, bv: int = 8,
                        interpret: bool | None = None,
                        mode: str = "dense_tile"):
    """``B`` independent patterns, H regenerated from the seed per tile.

    Same contract as :func:`decode_fused_batch_tiled` (values (B, V, N),
    erased_f (B, 1, N), both padded) minus the H operand: every grid step
    re-generates the tiles instead of re-streaming them, so the per-slot
    marginal HBM traffic is the payload alone.
    """
    interpret = detect_interpret(interpret)
    if mode == "gather":
        interpret_only("seeded_mode='gather'", interpret)
    B, V, N = values.shape
    _check_seeded_operands(spec, N, V, bp, bv)
    grid = (B, V // bv)
    return _pallas_call(
        "decode_seeded_batch",
        functools.partial(_decode_seeded_batch_kernel, spec=spec,
                          iters=iters, bp=bp, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, V, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, N), jnp.float32),
        ],
        interpret=interpret,
    )(values, erased_f)


def _decode_seeded_adaptive_kernel(vals_ref, erased_ref, out_vals_ref,
                                   out_erased_ref, out_rounds_ref, *, spec,
                                   max_iters: int, bp: int, mode: str):
    N = vals_ref.shape[1]
    round_body = _seeded_round_for(spec, mode, bp=bp,
                                   p_pad=_seeded_p_pad(spec, bp), n_pad=N)
    vals, e, d = _adaptive_loop(round_body, vals_ref[...], erased_ref[...],
                                max_iters)
    out_vals_ref[...] = vals
    out_erased_ref[...] = e
    out_rounds_ref[...] = jnp.full((1, 1), d, jnp.int32)


@functools.partial(jax.jit, static_argnames=("spec", "max_iters", "bp", "bv",
                                             "interpret", "mode"))
def decode_seeded_adaptive(spec, values: jax.Array, erased_f: jax.Array, *,
                           max_iters: int, bp: int = 128, bv: int = 8,
                           interpret: bool | None = None,
                           mode: str = "dense_tile"):
    """Early-exit decode with seed-regenerated tiles: an early exit stops
    the tile regeneration compute the way it stops the tiled kernel's H
    streaming.  Same stopping rule and outputs as
    :func:`decode_fused_adaptive` (values (V, N), erased (1, N), rounds
    (1, 1))."""
    interpret = detect_interpret(interpret)
    if mode == "gather":
        interpret_only("seeded_mode='gather'", interpret)
    V, N = values.shape
    _check_seeded_operands(spec, N, V, bp, bv)
    grid = (V // bv,)
    return _pallas_call(
        "decode_seeded_adaptive",
        functools.partial(_decode_seeded_adaptive_kernel, spec=spec,
                          max_iters=max_iters, bp=bp, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bv, N), lambda j: (j, 0)),
            pl.BlockSpec((1, N), lambda j: (0, 0)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((V, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(values, erased_f)


def _decode_seeded_batch_adaptive_kernel(vals_ref, erased_ref, budget_ref,
                                         out_vals_ref, out_erased_ref,
                                         out_rounds_ref, *, spec, bp: int,
                                         mode: str):
    N = vals_ref.shape[2]
    round_body = _seeded_round_for(spec, mode, bp=bp,
                                   p_pad=_seeded_p_pad(spec, bp), n_pad=N)
    vals, e, d = _adaptive_loop(round_body, vals_ref[0], erased_ref[0],
                                budget_ref[0, 0, 0])  # this slot's budget
    out_vals_ref[0] = vals
    out_erased_ref[0] = e
    out_rounds_ref[...] = jnp.full((1, 1, 1), d, jnp.int32)


@functools.partial(jax.jit, static_argnames=("spec", "bp", "bv", "interpret",
                                             "mode"))
def decode_seeded_batch_adaptive(spec, values: jax.Array,
                                 erased_f: jax.Array, budgets: jax.Array, *,
                                 bp: int = 128, bv: int = 8,
                                 interpret: bool | None = None,
                                 mode: str = "dense_tile"):
    """Per-slot adaptive decode of ``B`` patterns, seed-regenerated tiles.

    Same contract as :func:`decode_fused_batch_adaptive_tiled` (budgets
    (B, 1) int32 stays a TRACED operand) without the H operand: a light
    slot stops its regeneration compute after 1-2 rounds and no slot ever
    touches HBM for H.
    """
    interpret = detect_interpret(interpret)
    if mode == "gather":
        interpret_only("seeded_mode='gather'", interpret)
    B, V, N = values.shape
    _check_seeded_operands(spec, N, V, bp, bv)
    grid = (B, V // bv)
    vals, erased, rounds = _pallas_call(
        "decode_seeded_batch_adaptive",
        functools.partial(_decode_seeded_batch_adaptive_kernel, spec=spec,
                          bp=bp, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),      # slot budget
        ],
        out_specs=[
            pl.BlockSpec((1, bv, N), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, V, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, N), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(values, erased_f, budgets[:, :, None])
    return vals, erased, rounds[:, :, 0]


# ----------------------------------------------------- seeded fused encode --


def _encode_seeded_kernel(row0_ref, y_ref, out_ref, *, st, bo: int):
    """One ``(bo, bv)`` tile of seeded-LDGM codeword rows.

    Regenerates the generator gather table of each output row in-register
    — systematic rows are the identity gather, parity rows are the seeded
    draws sorted ASCENDING by column through an odd-even transposition
    network (``row_weight`` compare-exchange passes; columns within a row
    are distinct, so the network reproduces the host argsort exactly) —
    then accumulates ``sum_s w_s * y[col_s]`` as a SEQUENTIAL gather-FMA
    in table order, the same order ``repro.core.encoding.gather_encode``
    uses: the products and their addition order match bit for bit.
    """
    i = pl.program_id(0)
    K, rw = st.cols, st.row_weight
    N = st.cols + st.rows
    row = (row0_ref[0, 0] + i * bo
           + jax.lax.broadcasted_iota(jnp.int32, (bo, 1), 0))   # global row
    prow = row - K                         # parity row index (< 0: systematic)
    _, a, b, jl = _seeded_row_params(st, prow)

    pairs = []
    for s in range(rw):                    # static unroll: the r draws
        col = (a * (jl * rw + s) + b) % K
        pairs.append((col, _seeded_edge_weight(st, prow, s)))
    for p_ in range(rw):                   # odd-even transposition sort
        for q in range(p_ % 2, rw - 1, 2):
            c1, w1 = pairs[q]
            c2, w2 = pairs[q + 1]
            swap = c1 > c2
            pairs[q] = (jnp.where(swap, c2, c1), jnp.where(swap, w2, w1))
            pairs[q + 1] = (jnp.where(swap, c1, c2), jnp.where(swap, w1, w2))

    is_sys = row < K                       # systematic: identity gather
    is_par = (row >= K) & (row < N)        # pad rows (>= N): all-zero weights
    y = y_ref[...]                         # (K_pad, bv)
    acc = None
    for s in range(rw):                    # sequential FMA in table order
        c_s, w_s = pairs[s]
        if s == 0:
            c_s = jnp.where(is_sys, row, c_s)
            w_s = jnp.where(is_sys, 1.0, jnp.where(is_par, w_s, 0.0))
        else:
            c_s = jnp.where(is_sys, 0, c_s)
            w_s = jnp.where(is_sys, 0.0, jnp.where(is_par, w_s, 0.0))
        term = w_s * y[c_s[:, 0]]          # (bo, bv)
        acc = term if s == 0 else acc + term
    out_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("st", "n_out", "bo", "bv", "interpret"))
def encode_seeded_fused(st, y: jax.Array, row0: jax.Array, *, n_out: int,
                        bo: int = 128, bv: int = 128,
                        interpret: bool | None = None):
    """``n_out`` seeded-LDGM codeword rows starting at TRACED row ``row0``.

    ``st`` is the static :class:`repro.core.ldpc.SeededStructure` of the
    ``(p, K)`` generator parity block (``st.cols == K``); ``y`` is the
    already-padded payload (``(K_pad, V)`` f32, ``K_pad % 128 == 0``,
    ``V % bv == 0``, rows past ``K`` zero); ``row0`` a ``(1, 1)`` int32 —
    traced, so a shard_map'd worker passes ``axis_index * rows_per_worker``
    and every shard shares one compilation.  Rows at global index ``>= K +
    st.rows`` (output padding) come out exactly zero.  Returns ``(n_out,
    V)`` f32, bit-identical to ``gather_encode`` on the corresponding
    ``seeded_generator_rows`` table slice — but no table is ever
    materialized anywhere.
    """
    interpret = detect_interpret(interpret)
    interpret_only("the fused seeded encode kernel", interpret)
    K_pad, V = y.shape
    if K_pad % 128 or V % bv or K_pad < st.cols or n_out % bo or bo % 8:
        raise ValueError(
            "encode operands must be pre-padded (ops.py wrappers do this): "
            f"need K_pad % 128 == 0, V % bv == 0, K_pad >= st.cols, "
            f"n_out % bo == 0, bo % 8 == 0; got K_pad={K_pad} "
            f"(cols={st.cols}), V={V} bv={bv}, n_out={n_out} bo={bo}")
    grid = (n_out // bo, V // bv)
    return _pallas_call(
        "encode_seeded_fused",
        functools.partial(_encode_seeded_kernel, st=st, bo=bo),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),       # traced row0
            pl.BlockSpec((K_pad, bv), lambda i, j: (0, j)),  # payload tile
        ],
        out_specs=[pl.BlockSpec((bo, bv), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((n_out, V), jnp.float32)],
        interpret=interpret,
    )(row0, y)


# ------------------------------------------------------- schedule replay --


def _replay_edge_sum(nv, w):
    """``repro.core.decoder._edge_sum``'s exact op sequence, duplicated so
    kernels stay import-free of ``core.decoder`` (which imports ops.py):
    lone multiplies OUTSIDE a ``lax.scan``, Neumaier-compensated adds
    INSIDE it.  The scan boundary is what pins the IEEE op sequence
    per-element regardless of how many schedule entries the operand
    carries — must stay in lockstep with the decoder's copy for replay
    bit-parity."""
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


def _replay_kernel(nidx_ref, w_ref, coeff_ref, tgt_ref, vals_ref, erased_ref,
                   out_vals_ref, out_erased_ref, *, rounds: int, maxseg: int,
                   n_real: int):
    """Replay a packed peeling schedule: ``rounds`` segments of ``maxseg``
    entries each (sentinel-padded), every entry one resolving check's
    gather + compensated edge-sum + guarded divide, scattered back through
    an inverse-index gather (targets are unique within a round by
    construction, so a masked max over the entry axis recovers the writer
    exactly — the resolved value is MOVED, never re-accumulated, keeping
    its bits)."""
    nidx = nidx_ref[...]                            # (R*maxseg, r_max) i32
    w = w_ref[...]                                  # (R*maxseg, r_max) f32
    cf = coeff_ref[...][:, 0]                       # (R*maxseg,)
    tgt = tgt_ref[...][:, 0]                        # (R*maxseg,) i32
    n_pad = vals_ref.shape[0]

    ent = jax.lax.broadcasted_iota(jnp.int32, (maxseg, n_pad), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (maxseg, n_pad), 1)
    colv = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 1), 0)[:, 0]

    def round_body(t, carry):
        vals, e = carry
        b = t * maxseg
        idx_t = jax.lax.dynamic_slice_in_dim(nidx, b, maxseg)
        w_t = jax.lax.dynamic_slice_in_dim(w, b, maxseg)
        cf_t = jax.lax.dynamic_slice_in_dim(cf, b, maxseg)
        tg_t = jax.lax.dynamic_slice_in_dim(tgt, b, maxseg)
        nv = vals[idx_t]                            # (maxseg, r_max, BV)
        sums = _replay_edge_sum(nv, w_t)
        new_val = -sums / jnp.where(cf_t == 0.0, 1.0, cf_t)[:, None]
        # inverse-gather scatter: which entry (if any) writes each column
        inv = jnp.max(jnp.where(col == tg_t[:, None], ent, -1), axis=0)
        # sentinel targets land on padding columns; keep those rows exactly
        # +0.0 so later rounds' sentinel gathers read the same zero the
        # executor's concat row provides
        hit = (inv >= 0) & (colv < n_real)
        picked = new_val[jnp.maximum(inv, 0)]
        vals = jnp.where(hit[:, None], picked, vals)
        e = jnp.where(hit[:, None], 0.0, e)
        return vals, e

    vals, e = jax.lax.fori_loop(0, rounds, round_body,
                                (vals_ref[...], erased_ref[...]))
    out_vals_ref[...] = vals
    out_erased_ref[...] = e


@functools.partial(jax.jit, static_argnames=("rounds", "maxseg", "n_real",
                                             "bv", "interpret"))
def decode_replay(nidx: jax.Array, w: jax.Array, coeff: jax.Array,
                  tgt: jax.Array, values: jax.Array, erased_f: jax.Array, *,
                  rounds: int, maxseg: int, n_real: int, bv: int = 128,
                  interpret: bool | None = None):
    """Whole schedule replay in ONE ``pallas_call`` — no flooding loop, no
    convergence mask, no H operand: only the resolving checks' edges ride
    in as the packed schedule.

    Inputs (packed/padded by ops.py): ``nidx (R·maxseg, r_max) i32``
    neighbor columns (sentinel ``n_real`` on padding slots/entries — points
    at a guaranteed-zero padded row), ``w (R·maxseg, r_max) f32`` pre-masked
    edge weights, ``coeff (R·maxseg, 1) f32`` target-slot coefficients (0 on
    padding entries), ``tgt (R·maxseg, 1) i32`` target columns (sentinel
    ``n_real`` on padding entries), ``values (n_pad, V) f32`` with
    ``n_pad % 128 == 0`` and ``n_pad > n_real``, ``erased_f (n_pad, 1)``.

    ``interpret=None`` = backend-detected (compiled on TPU, else interpret).
    The schedule gathers lower like the seeded gather round — exact in
    interpret mode everywhere; TPU lowering tuning rides ROADMAP item 5.

    Returns (values (n_pad, V) f32, erased (n_pad, 1) f32).
    """
    interpret = detect_interpret(interpret)
    interpret_only("the fused replay kernel", interpret)
    n_pad, V = values.shape
    S, r_max = nidx.shape
    grid = (V // bv,)
    return _pallas_call(
        "decode_replay",
        functools.partial(_replay_kernel, rounds=rounds, maxseg=maxseg,
                          n_real=n_real),
        grid=grid,
        in_specs=[
            pl.BlockSpec((S, r_max), lambda j: (0, 0)),   # schedule: resident
            pl.BlockSpec((S, r_max), lambda j: (0, 0)),
            pl.BlockSpec((S, 1), lambda j: (0, 0)),
            pl.BlockSpec((S, 1), lambda j: (0, 0)),
            pl.BlockSpec((n_pad, bv), lambda j: (0, j)),  # payload slice
            pl.BlockSpec((n_pad, 1), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_pad, bv), lambda j: (0, j)),
            # every grid step replays the identical trajectory and rewrites
            # the same mask block — benign (sequential grid on TPU).
            pl.BlockSpec((n_pad, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, V), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(nidx, w, coeff, tgt, values, erased_f)
