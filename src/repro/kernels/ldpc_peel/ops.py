"""jit'd wrappers around the ldpc_peel kernels.

* :func:`peel_round_pallas` — one flooding round (``check_pass`` kernel +
  host-side scatter epilogue), kept for per-round experimentation/tests;
* :func:`peel_decode_pallas` — the fused path: pad ONCE, run the whole
  fixed-``D`` decode inside a single ``pallas_call`` (H resident in VMEM
  across rounds, scatter epilogue fused in-kernel), unpad once.  This is
  what ``repro.core.decoder.peel_decode(..., backend="pallas")`` calls.
* :func:`peel_decode_symbol_major_pallas` — the fixed-``D`` decode of a
  wide ``(N, V)`` payload without a transpose: :func:`peel_trajectory`
  solves the erasure trajectory once on H and the mask (a few XLA ops on
  the ``(p, N)`` H), then :func:`decode_symbol_major` makes one pass over
  lane tiles of the payload.  ``peel_decode(..., backend="pallas")``
  calls it where ``core/decoder.decode_layout`` says "symbol_major".
* :func:`peel_decode_edge_permuted_pallas` — the fixed-``D`` decode of a
  narrow payload on the code's edge network (``LDPCCode.edge_network``):
  no H operand; the payload is padded to whole vregs of the code axis, one
  grid step per payload column.  ``peel_decode(..., backend=
  "pallas_tiled")`` calls it on TPU where ``core/decoder.decode_layout``
  says "edge_permuted".
* :func:`peel_decode_batch_pallas` — ``B`` independent erasure patterns in
  one launch (grid over the batch, H resident and shared); the kernel side
  of ``CodedComputeEngine.decode_batch``.
* :func:`peel_decode_adaptive_pallas` — the early-exit decode as one launch
  (in-kernel ``while_loop`` on the unresolved count), so
  ``peel_decode_adaptive(backend="pallas")`` keeps single-launch parity with
  the fixed-D path.
* :func:`peel_decode_batch_adaptive_pallas` — per-slot adaptive decode of B
  independent patterns in one launch: grid over the slots, each with its own
  in-kernel ``while_loop`` and (traced) round budget; the kernel side of
  ``CodedComputeEngine.decode_batch(adaptive=True)`` and the serving
  layer's continuous-admission launches.
* the ``peel_decode*_tiled_pallas`` family — the same four contracts backed
  by the CHECK-AXIS-TILED kernels: H stays in HBM and is streamed
  tile-by-tile (``bp`` check rows at a time, double-buffered) while the
  value carry lives in VMEM, so problem size is no longer bounded by
  whole-H-in-VMEM.  The wrappers pad ``p`` up to a multiple of the
  effective ``bp`` (ragged tile edges become all-zero check rows: never
  counted, never solvable, never written), clamping ``bp`` down for small
  codes so a single-tile stream still works.

``interpret`` defaults to ``None`` = backend-detected: compiled on TPU,
interpret mode elsewhere (CPU CI runs the same kernel code path, slowly but
bit-faithfully).

The ``peel_decode*_seeded_pallas`` family wraps the SEEDED kernels: no H
argument at all — the caller passes the hashable
``repro.core.ldpc.SeededStructure`` spec (a static argument) and each tile
is regenerated in-register from the seed.  Only the payload is padded.
Each wrapper takes ``mode`` ("dense_tile" | "gather", static) selecting the
round implementation — dense regenerated-tile matmul vs the
edge-proportional gather/segment-sum round (same erasure trajectory,
O(p·r) instead of O(p·N) FLOPs per round).

:func:`peel_decode_replay_pallas` wraps the pattern-compiled REPLAY
kernel: it packs a pre-solved :class:`repro.core.decoder.PeelSchedule`
into sentinel-padded per-round segments (host-side, cached on the
schedule) and applies the whole elimination order in ONE ``pallas_call``
— no flooding loop, no H operand, values bit-identical to the
``backend="replay"`` executors under the matching tie-break rule.

:func:`encode_seeded_fused_pallas` is the ENCODE-side twin: the seeded
LDGM generator gather (``z = gather(G_rows, y)``) fused into one
``pallas_call`` that regenerates each output row's (column, weight) pairs
in-register — no ``(N, r+1)`` index tables materialized.  ``row0`` stays a
traced operand so sharded workers can encode their own row window without
recompiling per shard.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.benes import VREG_SLOTS
from repro.core.padding import pad_axis_to
from repro.kernels.ldpc_peel.kernel import (
    check_pass,
    decode_fused,
    decode_fused_adaptive,
    decode_fused_adaptive_tiled,
    decode_fused_batch,
    decode_fused_batch_adaptive,
    decode_fused_batch_adaptive_tiled,
    decode_fused_batch_tiled,
    decode_fused_tiled,
    decode_replay,
    decode_seeded,
    decode_seeded_adaptive,
    decode_seeded_batch,
    decode_seeded_batch_adaptive,
    decode_edge_permuted,
    decode_symbol_major,
    detect_interpret,
    encode_seeded_fused,
)

__all__ = ["peel_round_pallas", "peel_decode_pallas",
           "peel_decode_batch_pallas", "peel_decode_adaptive_pallas",
           "peel_decode_batch_adaptive_pallas",
           "peel_decode_tiled_pallas", "peel_decode_batch_tiled_pallas",
           "peel_decode_adaptive_tiled_pallas",
           "peel_decode_batch_adaptive_tiled_pallas",
           "peel_decode_seeded_pallas", "peel_decode_batch_seeded_pallas",
           "peel_decode_adaptive_seeded_pallas",
           "peel_decode_batch_adaptive_seeded_pallas",
           "encode_seeded_fused_pallas", "peel_decode_replay_pallas",
           "peel_decode_symbol_major_pallas", "peel_trajectory",
           "peel_decode_edge_permuted_pallas"]


@partial(jax.jit, static_argnames=("interpret", "bp", "bv"))
def _peel_round_impl(H, values, erased, *, interpret: bool,
                     bp: int = 128, bv: int = 128):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N = vals.shape[0]
    p = H.shape[0]

    bp_eff = min(bp, max(8, p))
    Hp = pad_axis_to(pad_axis_to(H.astype(jnp.float32), bp_eff, 0), 128, 1)
    vp = pad_axis_to(pad_axis_to(vals.astype(jnp.float32), 128, 0), bv, 1)
    ep = pad_axis_to(erased.astype(jnp.float32)[:, None], 128, 0)

    sums, cnt, pos, coeff = check_pass(Hp, vp, ep, bp=bp_eff,
                                       bv=min(bv, vp.shape[1]),
                                       interpret=interpret)
    sums, cnt, pos, coeff = (sums[:p, : vals.shape[1]], cnt[:p, 0],
                             pos[:p, 0], coeff[:p, 0])

    solvable = cnt == 1.0
    new_val = -sums / jnp.where(coeff == 0.0, 1.0, coeff)[:, None]
    safe_pos = jnp.where(solvable, pos, N)
    out_vals = vals.at[safe_pos].set(new_val.astype(vals.dtype), mode="drop")
    out_erased = erased.at[safe_pos].set(False, mode="drop")
    if squeeze:
        out_vals = out_vals[:, 0]
    return out_vals, out_erased


def peel_round_pallas(H, values, erased, *, interpret: bool | None = None,
                      bp: int = 128, bv: int = 128):
    """One flooding round. H (p,N) f32; values (N,) or (N,V); erased (N,) bool.
    Returns (values, erased) updated — same contract as decoder.peel_round."""
    return _peel_round_impl(H, values, erased,
                            interpret=detect_interpret(interpret),
                            bp=bp, bv=bv)


def _payload_rows(V: int, bv: int) -> int:
    """The kernels' payload tile height: ``bv`` rounded down to whole
    8-row sublane tiles, and no taller than ``V`` rounded up to one."""
    bv = max(8, bv - bv % 8)
    return min(bv, V + (-V) % 8)


def _lanes_in(vals, erased, bv):
    """``(…, N, V)`` payload + ``(…, N)`` bool mask → the kernels'
    LANE-MAJOR operands, padded once: values ``(…, Vp, Np)`` and mask
    ``(…, 1, Np)`` with the code axis on lanes (N → multiple of 128) and
    the payload on sublanes (V → whole ``bv`` tiles).  Returns ``(vp, ep,
    bv)`` with ``bv`` the payload tile height the kernels take.  Padded
    coordinates are "known" zeros on zero H columns: never counted, never
    solvable, never written."""
    bv = _payload_rows(vals.shape[-1], bv)
    vt = jnp.swapaxes(vals.astype(jnp.float32), -1, -2)
    vp = pad_axis_to(pad_axis_to(vt, 128, -1), bv, -2)
    ep = pad_axis_to(erased.astype(jnp.float32)[..., None, :], 128, -1)
    return vp, ep, bv


def _lanes_out(out_v, out_e, N: int, V: int, dtype):
    """Inverse of :func:`_lanes_in`: unpad and return ``(…, N, V)`` values
    and the ``(…, N)`` bool erasure mask."""
    vals = jnp.swapaxes(out_v[..., :V, :N], -1, -2).astype(dtype)
    return vals, out_e[..., 0, :N] > 0.0


def _pad_operands(H, vals, erased, bv, bp: int = 8):
    """Pad ONCE for a whole fused decode: H's p → multiple of ``bp`` (8
    sublanes for the resident kernels; the streamed tile height for the
    tiled ones, so every tile is full — ragged check-tile edges become
    all-zero rows: never counted, never solvable, never written) and
    N → multiple of 128 (lanes); the payload and mask as in
    :func:`_lanes_in`."""
    Hp = pad_axis_to(pad_axis_to(H.astype(jnp.float32), bp, 0), 128, 1)
    return (Hp, *_lanes_in(vals, erased, bv))


@partial(jax.jit, static_argnames=("iters", "interpret", "bv"))
def _peel_decode_impl(H, values, erased, *, iters: int, interpret: bool,
                      bv: int = 128):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N, V = vals.shape

    Hp, vp, ep, bv_ = _pad_operands(H, vals, erased, bv)
    out_v, out_e = decode_fused(Hp, vp, ep, iters=iters,
                                bv=bv_, interpret=interpret)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, 0]
    return out_vals, out_erased


def peel_decode_pallas(H, values, erased, iters: int, *,
                       interpret: bool | None = None, bv: int = 128):
    """Fixed-D decode in ONE kernel launch (no per-round relaunch/re-pad).

    H (p, N) f32; values (N,) or (N, V); erased (N,) bool.  Returns
    (values, erased) after exactly ``iters`` flooding rounds — same contract
    as ``decoder.peel_decode`` restricted to fixed D.
    """
    return _peel_decode_impl(H, values, erased, iters=int(iters),
                             interpret=detect_interpret(interpret), bv=bv)


def peel_trajectory(H, erased, iters: int, slots: int):
    """Solve a fixed-``iters`` flooding decode on the mask alone.

    The same rounds as :func:`repro.kernels.ldpc_peel.kernel
    ._check_tile_proposal`: a check with exactly one erased neighbour is
    solvable, it resolves that neighbour, and the lowest such check wins a
    coordinate.  None of it reads a payload value, so it runs once per
    decode on the ``(p, N)`` H, not once per payload tile.

    Returns the operands of :func:`decode_symbol_major` — ``tgt (N,)``,
    ``nbr (N·slots,)``, ``w (N·slots,)``, ``scale (N,)``, ``counts (2,)``
    — and the erasure mask left after ``iters`` rounds.  ``slots`` is at
    least the largest check degree less one.
    """
    p, N = H.shape
    Hb = H != 0.0
    col = jnp.arange(N, dtype=jnp.int32)
    row = jnp.arange(p, dtype=jnp.int32)[:, None]

    def round_(t, carry):
        e, when, chk = carry
        emask = Hb & e
        solvable = jnp.sum(emask, axis=1, keepdims=True) == 1
        pos = jnp.max(jnp.where(emask, col, -1), axis=1, keepdims=True)
        onehot = (col == pos) & solvable
        winner = jnp.min(jnp.where(onehot, row, p), axis=0)
        resolved = winner < p
        return (e & ~resolved, jnp.where(resolved, t, when),
                jnp.where(resolved, winner, chk))

    e0 = jnp.asarray(erased, bool)
    e, when, chk = jax.lax.fori_loop(
        0, iters, round_,
        (e0, jnp.full((N,), iters, jnp.int32), jnp.zeros((N,), jnp.int32)),
        unroll=True)
    # resolved in round order, then unresolved erased, then known
    key = jnp.where(e0, when, iters + 1)
    tgt = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.stack([jnp.sum(e0), jnp.sum(when < iters)]).astype(jnp.int32)

    h_row = H[chk[tgt]]                                        # (N, N)
    other = (h_row != 0.0) & (col != tgt[:, None])
    _, nbr = jax.lax.top_k(jnp.where(other, -col, -N - 1), slots)
    valid = jnp.take_along_axis(other, nbr, axis=1)
    w = jnp.where(valid, jnp.take_along_axis(h_row, nbr, axis=1), 0.0)
    nbr = jnp.where(valid, nbr, tgt[:, None]).astype(jnp.int32)
    coeff = jnp.take_along_axis(h_row, tgt[:, None], axis=1)[:, 0]
    scale = -1.0 / jnp.where(coeff == 0.0, 1.0, coeff)
    return (tgt, nbr.reshape(-1), w.reshape(-1).astype(jnp.float32),
            scale.astype(jnp.float32), counts), e


@partial(jax.jit, static_argnames=("iters", "slots", "bv", "chunk",
                                   "interpret"))
def _peel_decode_symbol_major_impl(H, values, erased, *, iters: int,
                                   slots: int, bv: int, chunk: int,
                                   interpret: bool):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    sched, out_e = peel_trajectory(H.astype(jnp.float32), erased, iters,
                                   slots)
    out_v = decode_symbol_major(*sched, vals.astype(jnp.float32), bv=bv,
                                chunk=chunk, interpret=interpret)
    out_v = out_v.astype(vals.dtype)
    return (out_v[:, 0] if squeeze else out_v), out_e


def peel_decode_symbol_major_pallas(H, values, erased, iters: int, *,
                                    max_degree: int, bv: int,
                                    interpret: bool | None = None):
    """Fixed-D decode of a wide payload in its own ``(N, V)`` layout.

    The erasure trajectory is solved once on H and the mask
    (:func:`peel_trajectory`), then one pass over ``bv``-lane tiles of
    the payload copies the known rows and computes only the resolved ones
    (:func:`decode_symbol_major`).  ``max_degree`` is the code's largest
    check degree; ``bv`` a multiple of 128 lanes
    (``core/decoder.pick_tile_lanes``).  Same trajectory and unresolved mask as
    :func:`peel_decode_pallas`, values to f32 summation order; erased
    coordinates left unresolved come back as 0 whatever the input holds
    there.
    """
    return _peel_decode_symbol_major_impl(
        H, values, erased, iters=int(iters), slots=max(int(max_degree) - 1, 1),
        bv=int(bv), chunk=128 * math.gcd(int(bv) // 128, 4),
        interpret=detect_interpret(interpret))


@partial(jax.jit, static_argnames=("iters", "geom", "interpret"))
def _peel_decode_edge_permuted_impl(words, coeff, values, erased, *,
                                    iters: int, geom, interpret: bool):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N, V = vals.shape
    Np = geom.var_vregs * VREG_SLOTS
    vt = pad_axis_to(vals.T.astype(jnp.float32), Np, 1)
    ep = pad_axis_to(erased.astype(jnp.float32), Np, 0)
    out_v, out_e = decode_edge_permuted(
        words, coeff, vt.reshape(-1, 8, 128), ep.reshape(-1, 8, 128),
        iters=iters, geom=geom, interpret=interpret)
    out_v = out_v.reshape(V, Np)[:, :N].T.astype(vals.dtype)
    return (out_v[:, 0] if squeeze else out_v), out_e.reshape(Np)[:N] > 0.0


def peel_decode_edge_permuted_pallas(network, values, erased, iters: int, *,
                                     interpret: bool | None = None):
    """Fixed-D decode on the code's edge network, with no H operand.

    ``network`` is the code's :class:`repro.core.benes.EdgeNetwork`
    (``LDPCCode.edge_network``); values (N,) or (N, V); erased (N,)
    bool.  Same trajectory and unresolved mask as
    :func:`peel_decode_tiled_pallas`, values to f32 summation order;
    unresolved coordinates keep their input values.  The network's tables
    enter as constants of the caller's program (a few hundred KiB)."""
    return _peel_decode_edge_permuted_impl(
        jnp.asarray(network.words.view(np.int32).reshape(-1, 8, 128)),
        jnp.asarray(network.coeff.reshape(-1, 8, 128)), values, erased,
        iters=int(iters), geom=network.geometry,
        interpret=detect_interpret(interpret))


@partial(jax.jit, static_argnames=("iters", "interpret", "bv"))
def _peel_decode_batch_impl(H, values, erased, *, iters: int, interpret: bool,
                            bv: int = 128):
    squeeze = values.ndim == 2  # (B, N) scalar payloads
    vals = values[:, :, None] if squeeze else values
    B, N, V = vals.shape

    Hp, vp, ep, bv_ = _pad_operands(H, vals, erased, bv)
    out_v, out_e = decode_fused_batch(Hp, vp, ep, iters=iters,
                                      bv=bv_,
                                      interpret=interpret)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, :, 0]
    return out_vals, out_erased


def peel_decode_batch_pallas(H, values, erased, iters: int, *,
                             interpret: bool | None = None, bv: int = 128):
    """Fixed-D decode of B independent erasure patterns in ONE launch.

    H (p, N) f32; values (B, N) or (B, N, V); erased (B, N) bool.  The grid
    runs over the batch with H resident in VMEM and shared across all B
    queries.  Returns (values, erased) with the batch axis preserved.
    """
    return _peel_decode_batch_impl(H, values, erased, iters=int(iters),
                                   interpret=detect_interpret(interpret),
                                   bv=bv)


@partial(jax.jit, static_argnames=("max_iters", "interpret", "bv"))
def _peel_decode_adaptive_impl(H, values, erased, *, max_iters: int,
                               interpret: bool, bv: int = 128):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N, V = vals.shape

    Hp, vp, ep, bv_ = _pad_operands(H, vals, erased, bv)
    out_v, out_e, rounds = decode_fused_adaptive(
        Hp, vp, ep, max_iters=max_iters, bv=bv_,
        interpret=interpret)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, 0]
    return out_vals, out_erased, rounds[0, 0]


def peel_decode_adaptive_pallas(H, values, erased, max_iters: int, *,
                                interpret: bool | None = None, bv: int = 128):
    """Early-exit decode in ONE launch (in-kernel while_loop).

    Same stopping rule as ``decoder.peel_decode_adaptive``: stop when a
    round resolves nothing, nothing is erased, or ``max_iters`` is reached.
    Returns (values, erased, rounds_used ()).
    """
    return _peel_decode_adaptive_impl(H, values, erased,
                                      max_iters=int(max_iters),
                                      interpret=detect_interpret(interpret),
                                      bv=bv)


@partial(jax.jit, static_argnames=("interpret", "bv"))
def _peel_decode_batch_adaptive_impl(H, values, erased, budgets, *,
                                     interpret: bool, bv: int = 128):
    squeeze = values.ndim == 2  # (B, N) scalar payloads
    vals = values[:, :, None] if squeeze else values
    B, N, V = vals.shape

    Hp, vp, ep, bv_ = _pad_operands(H, vals, erased, bv)
    out_v, out_e, rounds = decode_fused_batch_adaptive(
        Hp, vp, ep, budgets.astype(jnp.int32)[:, None],
        bv=bv_, interpret=interpret)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, :, 0]
    return out_vals, out_erased, rounds[:, 0]


def peel_decode_batch_adaptive_pallas(H, values, erased, budgets, *,
                                      interpret: bool | None = None,
                                      bv: int = 128):
    """Per-slot adaptive decode of B independent patterns in ONE launch.

    H (p, N) f32; values (B, N) or (B, N, V); erased (B, N) bool;
    budgets (B,) int — each slot's round budget (a traced operand: varying
    budgets never recompile).  Each slot follows exactly the
    ``decoder.peel_decode_adaptive`` stopping rule under its own budget.
    Returns (values, erased, rounds_used (B,)).
    """
    return _peel_decode_batch_adaptive_impl(
        H, values, erased, jnp.asarray(budgets),
        interpret=detect_interpret(interpret), bv=bv)


# ------------------------------------------------ check-axis-tiled family --


def _effective_bp(p: int, bp: int) -> int:
    """Clamp the check-tile height to the (8-aligned) padded check count so
    small codes stream as a single tile instead of over-padding."""
    p8 = p + (-p) % 8
    return max(8, min(bp - bp % 8 if bp >= 8 else 8, p8))


@partial(jax.jit, static_argnames=("iters", "interpret", "bp", "bv"))
def _peel_decode_tiled_impl(H, values, erased, *, iters: int, interpret: bool,
                            bp: int = 128, bv: int = 128):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N, V = vals.shape

    bp_eff = _effective_bp(H.shape[0], bp)
    Hp, vp, ep, bv_ = _pad_operands(H, vals, erased, bv, bp_eff)
    out_v, out_e = decode_fused_tiled(Hp, vp, ep, iters=iters, bp=bp_eff,
                                      bv=bv_,
                                      interpret=interpret)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, 0]
    return out_vals, out_erased


def peel_decode_tiled_pallas(H, values, erased, iters: int, *,
                             interpret: bool | None = None, bp: int = 128,
                             bv: int = 128):
    """Fixed-D decode in ONE launch with H streamed over check tiles.

    Same contract as :func:`peel_decode_pallas` (H (p, N) f32; values (N,)
    or (N, V); erased (N,) bool), same erasure trajectory; ``bp`` sets the
    streamed tile height (clamped/8-aligned, p padded up to a multiple).
    """
    return _peel_decode_tiled_impl(H, values, erased, iters=int(iters),
                                   interpret=detect_interpret(interpret),
                                   bp=bp, bv=bv)


@partial(jax.jit, static_argnames=("iters", "interpret", "bp", "bv"))
def _peel_decode_batch_tiled_impl(H, values, erased, *, iters: int,
                                  interpret: bool, bp: int = 128,
                                  bv: int = 128):
    squeeze = values.ndim == 2  # (B, N) scalar payloads
    vals = values[:, :, None] if squeeze else values
    B, N, V = vals.shape

    bp_eff = _effective_bp(H.shape[0], bp)
    Hp, vp, ep, bv_ = _pad_operands(H, vals, erased, bv, bp_eff)
    out_v, out_e = decode_fused_batch_tiled(Hp, vp, ep, iters=iters,
                                            bp=bp_eff,
                                            bv=bv_,
                                            interpret=interpret)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, :, 0]
    return out_vals, out_erased


def peel_decode_batch_tiled_pallas(H, values, erased, iters: int, *,
                                   interpret: bool | None = None,
                                   bp: int = 128, bv: int = 128):
    """Fixed-D decode of B independent patterns, H streamed over check
    tiles.  Same contract as :func:`peel_decode_batch_pallas`."""
    return _peel_decode_batch_tiled_impl(
        H, values, erased, iters=int(iters),
        interpret=detect_interpret(interpret), bp=bp, bv=bv)


@partial(jax.jit, static_argnames=("max_iters", "interpret", "bp", "bv"))
def _peel_decode_adaptive_tiled_impl(H, values, erased, *, max_iters: int,
                                     interpret: bool, bp: int = 128,
                                     bv: int = 128):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N, V = vals.shape

    bp_eff = _effective_bp(H.shape[0], bp)
    Hp, vp, ep, bv_ = _pad_operands(H, vals, erased, bv, bp_eff)
    out_v, out_e, rounds = decode_fused_adaptive_tiled(
        Hp, vp, ep, max_iters=max_iters, bp=bp_eff,
        bv=bv_, interpret=interpret)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, 0]
    return out_vals, out_erased, rounds[0, 0]


def peel_decode_adaptive_tiled_pallas(H, values, erased, max_iters: int, *,
                                      interpret: bool | None = None,
                                      bp: int = 128, bv: int = 128):
    """Early-exit decode in ONE launch, H streamed over check tiles.  Same
    stopping rule and contract as :func:`peel_decode_adaptive_pallas`."""
    return _peel_decode_adaptive_tiled_impl(
        H, values, erased, max_iters=int(max_iters),
        interpret=detect_interpret(interpret), bp=bp, bv=bv)


@partial(jax.jit, static_argnames=("interpret", "bp", "bv"))
def _peel_decode_batch_adaptive_tiled_impl(H, values, erased, budgets, *,
                                           interpret: bool, bp: int = 128,
                                           bv: int = 128):
    squeeze = values.ndim == 2  # (B, N) scalar payloads
    vals = values[:, :, None] if squeeze else values
    B, N, V = vals.shape

    bp_eff = _effective_bp(H.shape[0], bp)
    Hp, vp, ep, bv_ = _pad_operands(H, vals, erased, bv, bp_eff)
    out_v, out_e, rounds = decode_fused_batch_adaptive_tiled(
        Hp, vp, ep, budgets.astype(jnp.int32)[:, None], bp=bp_eff,
        bv=bv_, interpret=interpret)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, :, 0]
    return out_vals, out_erased, rounds[:, 0]


def peel_decode_batch_adaptive_tiled_pallas(H, values, erased, budgets, *,
                                            interpret: bool | None = None,
                                            bp: int = 128, bv: int = 128):
    """Per-slot adaptive decode of B independent patterns in ONE launch,
    H streamed over check tiles per slot.  Same contract as
    :func:`peel_decode_batch_adaptive_pallas` (budgets stay traced)."""
    return _peel_decode_batch_adaptive_tiled_impl(
        H, values, erased, jnp.asarray(budgets),
        interpret=detect_interpret(interpret), bp=bp, bv=bv)


# ------------------------------------------------------- seeded family --
#
# Only the payload is padded: there is no H operand, and the generated
# tiles are zero on padded columns and check rows by construction.


@partial(jax.jit, static_argnames=("spec", "iters", "interpret", "bp", "bv",
                                   "mode"))
def _peel_decode_seeded_impl(values, erased, *, spec, iters: int,
                             interpret: bool, bp: int = 128, bv: int = 128,
                             mode: str = "dense_tile"):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N, V = vals.shape

    bp_eff = _effective_bp(spec.rows, bp)
    vp, ep, bv_ = _lanes_in(vals, erased, bv)
    out_v, out_e = decode_seeded(spec, vp, ep, iters=iters, bp=bp_eff,
                                 bv=bv_, interpret=interpret,
                                 mode=mode)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, 0]
    return out_vals, out_erased


def peel_decode_seeded_pallas(spec, values, erased, iters: int, *,
                              interpret: bool | None = None, bp: int = 128,
                              bv: int = 128, mode: str = "dense_tile"):
    """Fixed-D decode in ONE launch with H REGENERATED from the seed.

    ``spec`` is the static :class:`repro.core.ldpc.SeededStructure`; values
    (N,) or (N, V); erased (N,) bool.  Same erasure trajectory as every
    materialized backend on the same code and bit-identical VALUES to the
    tiled path (same tile-shaped summation); zero H operand traffic.
    ``mode="gather"`` swaps the dense regenerated-tile round for the
    edge-proportional gather round: identical trajectory, values equal up
    to f32 summation order.
    """
    return _peel_decode_seeded_impl(values, erased, spec=spec,
                                    iters=int(iters),
                                    interpret=detect_interpret(interpret),
                                    bp=bp, bv=bv, mode=mode)


@partial(jax.jit, static_argnames=("spec", "iters", "interpret", "bp", "bv",
                                   "mode"))
def _peel_decode_batch_seeded_impl(values, erased, *, spec, iters: int,
                                   interpret: bool, bp: int = 128,
                                   bv: int = 128, mode: str = "dense_tile"):
    squeeze = values.ndim == 2  # (B, N) scalar payloads
    vals = values[:, :, None] if squeeze else values
    B, N, V = vals.shape

    bp_eff = _effective_bp(spec.rows, bp)
    vp, ep, bv_ = _lanes_in(vals, erased, bv)
    out_v, out_e = decode_seeded_batch(spec, vp, ep, iters=iters, bp=bp_eff,
                                       bv=bv_,
                                       interpret=interpret, mode=mode)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, :, 0]
    return out_vals, out_erased


def peel_decode_batch_seeded_pallas(spec, values, erased, iters: int, *,
                                    interpret: bool | None = None,
                                    bp: int = 128, bv: int = 128,
                                    mode: str = "dense_tile"):
    """Fixed-D decode of B independent patterns, H regenerated from the
    seed per grid step.  Same contract as
    :func:`peel_decode_batch_tiled_pallas` minus the H operand;
    ``mode="gather"`` selects the edge-proportional round."""
    return _peel_decode_batch_seeded_impl(
        values, erased, spec=spec, iters=int(iters),
        interpret=detect_interpret(interpret), bp=bp, bv=bv, mode=mode)


@partial(jax.jit,
         static_argnames=("spec", "max_iters", "interpret", "bp", "bv",
                          "mode"))
def _peel_decode_adaptive_seeded_impl(values, erased, *, spec,
                                      max_iters: int, interpret: bool,
                                      bp: int = 128, bv: int = 128,
                                      mode: str = "dense_tile"):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N, V = vals.shape

    bp_eff = _effective_bp(spec.rows, bp)
    vp, ep, bv_ = _lanes_in(vals, erased, bv)
    out_v, out_e, rounds = decode_seeded_adaptive(
        spec, vp, ep, max_iters=max_iters, bp=bp_eff,
        bv=bv_, interpret=interpret, mode=mode)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, 0]
    return out_vals, out_erased, rounds[0, 0]


def peel_decode_adaptive_seeded_pallas(spec, values, erased, max_iters: int,
                                       *, interpret: bool | None = None,
                                       bp: int = 128, bv: int = 128,
                                       mode: str = "dense_tile"):
    """Early-exit decode in ONE launch, H regenerated from the seed.  Same
    stopping rule and contract as :func:`peel_decode_adaptive_tiled_pallas`
    minus the H operand; ``mode="gather"`` selects the edge-proportional
    round (identical trajectory and round counts)."""
    return _peel_decode_adaptive_seeded_impl(
        values, erased, spec=spec, max_iters=int(max_iters),
        interpret=detect_interpret(interpret), bp=bp, bv=bv, mode=mode)


@partial(jax.jit, static_argnames=("spec", "interpret", "bp", "bv", "mode"))
def _peel_decode_batch_adaptive_seeded_impl(values, erased, budgets, *, spec,
                                            interpret: bool, bp: int = 128,
                                            bv: int = 128,
                                            mode: str = "dense_tile"):
    squeeze = values.ndim == 2  # (B, N) scalar payloads
    vals = values[:, :, None] if squeeze else values
    B, N, V = vals.shape

    bp_eff = _effective_bp(spec.rows, bp)
    vp, ep, bv_ = _lanes_in(vals, erased, bv)
    out_v, out_e, rounds = decode_seeded_batch_adaptive(
        spec, vp, ep, budgets.astype(jnp.int32)[:, None], bp=bp_eff,
        bv=bv_, interpret=interpret, mode=mode)
    out_vals, out_erased = _lanes_out(out_v, out_e, N, V, vals.dtype)
    if squeeze:
        out_vals = out_vals[:, :, 0]
    return out_vals, out_erased, rounds[:, 0]


def peel_decode_batch_adaptive_seeded_pallas(spec, values, erased, budgets,
                                             *, interpret: bool | None = None,
                                             bp: int = 128, bv: int = 128,
                                             mode: str = "dense_tile"):
    """Per-slot adaptive decode of B independent patterns in ONE launch, H
    regenerated from the seed per slot.  Same contract as
    :func:`peel_decode_batch_adaptive_tiled_pallas` (budgets stay traced);
    ``mode="gather"`` selects the edge-proportional round."""
    return _peel_decode_batch_adaptive_seeded_impl(
        values, erased, jnp.asarray(budgets), spec=spec,
        interpret=detect_interpret(interpret), bp=bp, bv=bv, mode=mode)


# ------------------------------------------------------- seeded encode --


@partial(jax.jit, static_argnames=("st", "n_out", "interpret", "bo", "bv"))
def _encode_seeded_fused_impl(y, row0, *, st, n_out: int, interpret: bool,
                              bo: int = 128, bv: int = 128):
    squeeze = y.ndim == 1
    yv = y[:, None] if squeeze else y
    V = yv.shape[1]

    bo_eff = _effective_bp(n_out, bo)
    n_pad = n_out + (-n_out) % bo_eff
    yp = pad_axis_to(pad_axis_to(yv.astype(jnp.float32), 128, 0), bv, 1)
    out = encode_seeded_fused(st, yp, row0, n_out=n_pad, bo=bo_eff,
                              bv=min(bv, yp.shape[1]),
                              interpret=interpret)[0]
    out = out[:n_out, :V].astype(yv.dtype)
    if squeeze:
        out = out[:, 0]
    return out


def encode_seeded_fused_pallas(st, y, row0=0, *, n_out: int | None = None,
                               interpret: bool | None = None,
                               bo: int = 128, bv: int = 128):
    """Seeded-LDGM codeword rows ``[row0, row0 + n_out)`` from payload ``y``,
    generator gather fused into ONE kernel launch — no index tables.

    ``st`` is the static :class:`repro.core.ldpc.SeededStructure` of the
    generator's parity block (``st.cols == K``, ``st.rows == p``); ``y`` is
    (K,) or (K, V); ``row0`` may be a traced int (sharded workers pass
    ``axis_index * rows_per_worker``).  ``n_out`` defaults to the full
    codeword length ``K + p``; rows past it are computed in padding and
    sliced away, rows at global index ``>= K + p`` are exactly zero.  The
    per-row gather-sum runs in TABLE order, bit-identical to the
    (jit-compiled) :func:`repro.core.encoding.gather_encode` over
    ``seeded_generator_rows``.
    """
    if n_out is None:
        n_out = st.cols + st.rows
    r0 = jnp.asarray(row0, jnp.int32).reshape(1, 1)
    return _encode_seeded_fused_impl(y, r0, st=st, n_out=int(n_out),
                                     interpret=detect_interpret(interpret),
                                     bo=bo, bv=bv)


# ----------------------------------------------------- schedule replay --


def _pack_replay(sched, rule: str, rounds: int):
    """Pack ``rounds`` schedule segments into dense sentinel-padded arrays
    for the fused replay kernel: every round becomes ``maxseg`` entries
    (real ones first, then no-op padding whose neighbor/target indices are
    the sentinel ``N`` — a guaranteed-zero padded row/column).  Built
    host-side once per ``(rule, rounds)`` prefix and cached on the
    schedule next to the executor operands."""
    key = ("packed", rule, rounds)
    cached = sched._ops.get(key)
    if cached is not None:
        return cached
    off = np.asarray(sched.offsets)
    segs = [(int(off[k]), int(off[k + 1])) for k in range(rounds)]
    maxseg = max([s1 - s0 for s0, s1 in segs] + [1])
    R = max(rounds, 1)
    nidx = np.full((R * maxseg, sched.r_max), sched.N, np.int32)
    w = np.zeros((R * maxseg, sched.r_max), np.float32)
    cf = np.zeros((R * maxseg, 1), np.float32)
    tg = np.full((R * maxseg, 1), sched.N, np.int32)
    src_i = getattr(sched, f"idx_{rule}")
    src_w = getattr(sched, f"w_{rule}")
    src_c = getattr(sched, f"coeff_{rule}")
    for k, (s0, s1) in enumerate(segs):
        n = s1 - s0
        nidx[k * maxseg:k * maxseg + n] = src_i[s0:s1]
        w[k * maxseg:k * maxseg + n] = src_w[s0:s1]
        cf[k * maxseg:k * maxseg + n, 0] = src_c[s0:s1]
        tg[k * maxseg:k * maxseg + n, 0] = sched.target[s0:s1]
    # concrete even if first packed under a caller's jit trace — cached
    # tracers would poison later eager replays of the same schedule
    with jax.ensure_compile_time_eval():
        cached = (jnp.asarray(nidx), jnp.asarray(w), jnp.asarray(cf),
                  jnp.asarray(tg), maxseg)
    sched._ops[key] = cached
    return cached


@partial(jax.jit, static_argnames=("rounds", "maxseg", "n_real", "interpret",
                                   "bv"))
def _peel_decode_replay_impl(nidx, w, cf, tg, values, erased, *, rounds: int,
                             maxseg: int, n_real: int, interpret: bool,
                             bv: int = 128):
    squeeze = values.ndim == 1
    vals = values[:, None] if squeeze else values
    N, V = vals.shape
    # pad N past the sentinel row (n_pad > N always) and up to the lane
    # multiple; sentinel gathers then read a real zero row, exactly like
    # the executors' concatenated zero row
    n_pad = N + 1 + (-(N + 1)) % 128
    vp = jnp.concatenate([vals.astype(jnp.float32),
                          jnp.zeros((n_pad - N, V), jnp.float32)])
    vp = pad_axis_to(vp, bv, -1)
    ep = jnp.concatenate([erased.astype(jnp.float32)[:, None],
                          jnp.zeros((n_pad - N, 1), jnp.float32)])
    out_v, out_e = decode_replay(nidx, w, cf, tg, vp, ep, rounds=rounds,
                                 maxseg=maxseg, n_real=n_real,
                                 bv=min(bv, vp.shape[1]), interpret=interpret)
    out_vals = out_v[:N, :V].astype(vals.dtype)
    out_erased = out_e[:N, 0] > 0.0
    if squeeze:
        out_vals = out_vals[:, 0]
    return out_vals, out_erased


def peel_decode_replay_pallas(sched, values, erased, rounds: int | None = None,
                              *, rule: str = "hi",
                              interpret: bool | None = None, bv: int = 128):
    """Replay a pre-solved peeling schedule in ONE kernel launch.

    ``sched`` is a :class:`repro.core.decoder.PeelSchedule` (passed
    duck-typed — ops stays import-free of ``core.decoder``); values (N,)
    or (N, V); erased (N,) bool.  ``rounds`` clips the replayed prefix
    (default: the whole schedule — budgets are host-known whenever the
    schedule is, so budget clipping is a pack-time slice, not a traced
    mask).  ``rule`` picks the duplicate-check tie-break: ``"hi"`` matches
    the single-pattern dense/sparse scatter (and ``backend="replay"``'s
    single-pattern executor), ``"lo"`` the batch-major/kernel merges.
    Values are bit-identical to the matching executor; work is
    O(schedule entries · r_max).
    """
    rounds = sched.n_rounds if rounds is None else min(int(rounds),
                                                       sched.n_rounds)
    nidx, w, cf, tg, maxseg = _pack_replay(sched, rule, rounds)
    return _peel_decode_replay_impl(nidx, w, cf, tg, values, erased,
                                    rounds=rounds, maxseg=maxseg,
                                    n_real=sched.N,
                                    interpret=detect_interpret(interpret),
                                    bv=bv)
