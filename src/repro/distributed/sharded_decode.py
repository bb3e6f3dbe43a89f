"""Sharded master decode: check tiles partitioned over the workers mesh.

Once N outgrows one device, the master's peeling decode itself must shard.
The peeling update is per-variable OVERWRITE semantics (a solvable check
writes its resolved neighbour's value), NOT an f32 contraction — so unlike
the gradient epilogue it shards WITHOUT changing any summation order: each
check row's sum stays entirely inside the shard that owns the row, and the
cross-shard merge is a select, not an add.  That is what makes the sharded
decode bit-identical to the single-device one (proved by
``repro.distributed.selfcheck --master-decode sharded`` and
``tests/test_distributed.py`` on the fake 8-device mesh).

Layout: the CHECK-side neighbor table (``check_idx`` / ``check_coeff``,
padded so the check count divides the mesh — pad rows are degree-0 checks:
sentinel-indexed, zero-weighted, never solvable) is partitioned
``P("workers", None)`` over the mesh's ``"workers"`` axis; the value vector
and erasure mask stay replicated.  Each round, every device runs the SAME
:func:`repro.core.decoder.peel_round_sparse` the single-device master runs,
restricted to its own check rows, and the per-shard results are
all-gathered ONCE and merged in ascending device order with
later-shard-overwrites.  Ascending contiguous row shards make that merge
order exactly the ascending-check-row order in which XLA applies the
single-device round's duplicate scatter updates (updates are applied in
operand order), so even the rare same-round duplicate resolutions land on
identical bits.  (Scatter duplicate order is implementation-defined in HLO;
the selfcheck is the guard on any backend where it differs.)

Budget policy mirrors the single-device master: the fixed mode runs a
static number of rounds; the telemetry mode takes the round budget as a
TRACED ``(1,)`` operand and early-exits on the shared
no-progress/nothing-erased/budget-exhausted predicate (computed from the
replicated mask, so every device agrees), returning the rounds spent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core.decoder import peel_round_sparse
from repro.core.ldpc import LDPCCode

__all__ = ["pad_check_tables", "shard_check_tables", "build_sharded_decode"]


def pad_check_tables(code: LDPCCode, n_shards: int
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Check-side neighbor table padded so ``p`` divides ``n_shards``.

    Pad rows are degree-0 checks (``check_idx`` = the sentinel ``N``,
    ``check_coeff`` = 0): their erased-neighbour count is always 0, so they
    are never solvable and never write — the padded decode follows the
    unpadded trajectory exactly.
    """
    idx, coeff = code.check_idx, code.check_coeff
    p, r_max = idx.shape
    pad = (-p) % n_shards
    if pad:
        idx = np.concatenate(
            [idx, np.full((pad, r_max), code.N, np.int32)])
        coeff = np.concatenate([coeff, np.zeros((pad, r_max), np.float32)])
    return idx, coeff


def shard_check_tables(code: LDPCCode, mesh: Mesh,
                       axis: str = "workers") -> tuple[jax.Array, jax.Array]:
    """``device_put`` the (padded) check tables row-sharded over ``axis``."""
    n_dev = mesh.shape[axis]
    idx, coeff = pad_check_tables(code, n_dev)
    sh = NamedSharding(mesh, P(axis, None))
    return jax.device_put(idx, sh), jax.device_put(coeff, sh)


def build_sharded_decode(mesh: Mesh, *, iters: int, adaptive: bool = False,
                         axis: str = "workers"):
    """The sharded fixed-D / adaptive peeling decode over ``mesh``.

    Returns ``decode(check_idx_sh, check_coeff_sh, values, erased, budget)``
    → ``(values, erased, rounds ()i32)`` where the tables are row-sharded
    ``P(axis, None)`` (see :func:`shard_check_tables`), ``values (N, V)``
    and ``erased (N,) bool`` are replicated, and ``budget (1,) int32`` is
    the traced round cap of the adaptive mode (ignored — rounds ==
    ``iters`` — when ``adaptive=False``).  The function is shard_map-ped
    but NOT jitted; callers jit the surrounding master program.
    """
    n_dev = mesh.shape[axis]

    def local_decode(idx_sh, coeff_sh, values, erased, budget):
        # Runs per device: idx/coeff are this device's check rows; values,
        # erased, and budget are replicated (identical on every device).
        def round_body(v, e):
            v_d, e_d = peel_round_sparse(idx_sh, coeff_sh, v, e)
            resolved_d = e & ~e_d                          # (N,)
            # ONE all-gather of the round's per-shard results ...
            V_all = jax.lax.all_gather(v_d, axis)          # (W, N, V)
            R_all = jax.lax.all_gather(resolved_d, axis)   # (W, N)

            # ... merged in ascending device order, later shard overwrites:
            # == ascending global check-row order == the order XLA applies
            # the single-device scatter's duplicate updates.  Pure selects —
            # no f32 sum crosses a shard boundary.
            def merge(d, carry):
                v_, e_ = carry
                r = jax.lax.dynamic_index_in_dim(R_all, d, keepdims=False)
                vd = jax.lax.dynamic_index_in_dim(V_all, d, keepdims=False)
                return jnp.where(r[:, None], vd, v_), e_ & ~r

            return jax.lax.fori_loop(0, n_dev, merge, (v, e))

        if not adaptive:
            vals, e = jax.lax.fori_loop(
                0, iters, lambda _, c: round_body(*c), (values, erased))
            return vals, e, jnp.int32(iters)

        def cond(carry):
            _, e, d, progressed = carry
            return (d < budget[0]) & progressed & e.any()

        def body(carry):
            v, e, d, _ = carry
            v2, e2 = round_body(v, e)
            return v2, e2, d + 1, (e2 != e).any()

        vals, e, d, _ = jax.lax.while_loop(
            cond, body, (values, erased, jnp.int32(0), jnp.bool_(True)))
        return vals, e, d

    return shard_map(
        local_decode, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False)
