"""Pallas LDPC peeling-decoder kernels.

``peel_decode_pallas`` is the fused hot path: the whole fixed-D decode in
one kernel launch (see ops.py / kernel.py for the backend matrix and
interpret-mode behaviour off-TPU).  ``peel_decode_batch_pallas`` extends it
with a first-class batch axis over independent erasure patterns (grid over
the batch, H resident in VMEM and shared), ``peel_decode_adaptive_pallas``
runs the early-exit decode as one launch via an in-kernel while_loop, and
``peel_decode_batch_adaptive_pallas`` combines the two axes: per-slot
adaptive early exit (with per-slot round budgets) across a batch of
independent erasure patterns, still one launch.

The ``peel_decode*_tiled_pallas`` family carries the same four contracts
past the whole-H-in-VMEM limit: H stays in HBM and is streamed over CHECK
tiles (``bp`` rows at a time, double-buffered DMA) while the value carry
lives in VMEM — one launch, same erasure trajectories, problem size bounded
by HBM instead of one core's VMEM.  ``peel_round_pallas`` keeps the
single-round check-pass path for experimentation and tests.

The ``peel_decode*_seeded_pallas`` family goes one step further: NO H
operand at all.  The caller passes a hashable
``repro.core.ldpc.SeededStructure`` and each ``bp x N`` check tile is
regenerated in-register from the seed inside the flooding round
(``seeded_h_tile``), so H costs zero bytes of HBM storage and traffic —
same erasure trajectories, values bit-identical to the tiled path.

``peel_decode_symbol_major_pallas`` is the fixed-D decode of a payload
wide against the code: the trajectory is solved once per call on H and
the mask, and one pass over lane tiles of the untransposed ``(N, V)``
payload touches only the rows it resolves.

``peel_decode_replay_pallas`` drops the round structure entirely: it takes
a precompiled ``repro.core.PeelSchedule`` (value-independent elimination
order) and replays the resolved edges as one fused gather/FMA launch —
O(resolved edges) work instead of O(rounds x p x r_max), bit-identical to
the flooding trajectory under the matching tie-break rule.
"""
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
    decode_symbol_major,
    seeded_h_tile,
)
from repro.kernels.ldpc_peel.ops import (
    peel_decode_adaptive_pallas,
    peel_decode_adaptive_seeded_pallas,
    peel_decode_adaptive_tiled_pallas,
    peel_decode_batch_adaptive_pallas,
    peel_decode_batch_adaptive_seeded_pallas,
    peel_decode_batch_adaptive_tiled_pallas,
    peel_decode_batch_pallas,
    peel_decode_batch_seeded_pallas,
    peel_decode_batch_tiled_pallas,
    peel_decode_pallas,
    peel_decode_replay_pallas,
    peel_decode_seeded_pallas,
    peel_decode_symbol_major_pallas,
    peel_decode_tiled_pallas,
    peel_round_pallas,
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
           "peel_decode_replay_pallas", "peel_decode_symbol_major_pallas",
           "check_pass", "decode_fused", "decode_fused_batch",
           "decode_fused_adaptive", "decode_fused_batch_adaptive",
           "decode_fused_tiled", "decode_fused_batch_tiled",
           "decode_fused_adaptive_tiled",
           "decode_fused_batch_adaptive_tiled",
           "decode_replay", "decode_symbol_major",
           "decode_seeded", "decode_seeded_batch",
           "decode_seeded_adaptive", "decode_seeded_batch_adaptive",
           "seeded_h_tile"]
