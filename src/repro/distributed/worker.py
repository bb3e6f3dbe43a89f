"""Worker side of the distributed coded pipeline.

Each worker owns a contiguous shard of the encoded moment's rows (its slice
of ``C = G·M``) and, per step, computes the partial products for exactly
those rows — ``z_local = C_shard @ θ`` — then reports them to the master.
A straggling worker reports nothing, which the master sees as the erasure
of ALL of that worker's rows: straggler injection is realized here at
per-WORKER granularity (``StragglerModel`` masks sampled at width ``W``
and lifted through :meth:`repro.distributed.topology.WorkerTopology
.to_symbol_erasure`), not per-symbol as the single-device simulation does.

:func:`build_worker_products` returns the ``shard_map``-ped compute over
the mesh's ``"workers"`` axis.  Inside the mapped function every device
sees only its own row shard — the per-device working set is
``(N / n_devices) × k``, which is what lets the encoded operator scale past
single-device memory.  The erasure zeroing ALSO runs worker-side (a real
straggler never sends bytes); the master re-applies its own mask when it
decodes, so the two layers cannot disagree.

SEEDED workers (:func:`local_products_seeded` /
:func:`build_seeded_worker_products`): for a seeded LDGM code the worker
never holds its rows of the encoding matrix AT ALL — it keeps only its
``(rows/device, row_weight)`` slice of the generator gather tables
(regenerable from ``(seed, row)``; :func:`shard_generator_tables`) and
fuses encode into the matvec: ``y = M θ`` (replicated — the same bits on
every device), then ``z_local = Σ_s coeff·y[idx]`` over its rows.  This is
the SAME per-row gather+sum the single-device seeded
``Scheme2.build_seeded`` runs, so distributed products are bit-identical
to the single-device ones; the per-device structure footprint drops from
``(N/W)·k`` floats to ``(N/W)·row_weight`` table entries.
:func:`build_seeded_fused_worker_products` goes one step further: the
gather runs inside the fused Pallas encode kernel with indices regenerated
in-register from the seed, so workers hold NO tables at all (structure
footprint: a few ints).

The worker payload may be 2-D: ``theta (k, dim)`` (coded gradient
AGGREGATION, where each systematic symbol is a flattened partial gradient)
produces ``z (rows, dim)`` — the same row-sharded program serves
:class:`repro.distributed.master.DistributedCodedAggregator`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.lax import Precision
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.encoding import gather_encode, generator_structure_of
from repro.core.ldpc import LDPCCode, seeded_generator_rows
from repro.core.straggler import StragglerModel
from repro.distributed.topology import WorkerTopology, row_sharding

__all__ = ["WorkerStragglers", "local_products", "build_worker_products",
           "shard_encoded_rows", "local_products_seeded",
           "build_seeded_worker_products", "shard_generator_tables",
           "build_seeded_fused_worker_products"]


@dataclasses.dataclass(frozen=True)
class WorkerStragglers:
    """A per-symbol :class:`~repro.core.straggler.StragglerModel`, lifted to
    per-WORKER granularity: sample a (W,) worker mask, then expand it to the
    (N,) symbol erasure through the topology's row assignment.

    Any model satisfying the ``StragglerModel`` protocol lifts unchanged —
    the protocol's width argument is simply the worker count instead of the
    symbol count (Bernoulli q0 per worker, exactly-s workers, adversarial
    fixed worker sets, ...).
    """

    model: StragglerModel
    topology: WorkerTopology

    def sample_workers(self, key: jax.Array) -> jax.Array:
        """(W,) bool — which workers straggle this step."""
        return self.model.sample(key, self.topology.n_workers)

    def sample(self, key: jax.Array, w: int) -> jax.Array:
        """StragglerModel protocol: (N,) symbol mask (for drop-in use by
        ``run_pgd``-style drivers that expect per-symbol masks)."""
        if w != self.topology.N:
            raise ValueError(f"expected symbol width {self.topology.N}, got {w}")
        return self.topology.to_symbol_erasure(self.sample_workers(key))


def local_products(C_shard: jax.Array, theta: jax.Array,
                   erased_shard: jax.Array) -> jax.Array:
    """One worker shard's step: partial products, zeroed if straggling.

    Runs INSIDE ``shard_map`` — ``C_shard`` is this device's
    ``(rows/device, k)`` slice, ``theta`` is replicated, ``erased_shard``
    this device's slice of the symbol erasure mask.  Row-block matvecs are
    bitwise identical to the corresponding rows of the full ``C @ θ`` (each
    output element is an independent dot product), which is what makes the
    distributed trajectory reproduce the single-device one bit-for-bit.

    ``theta`` may also be a 2-D ``(k, dim)`` payload (coded gradient
    aggregation) — ``z`` is then ``(rows, dim)`` with the erasure mask
    broadcast over the payload axis.
    """
    z = jnp.matmul(C_shard, theta, precision=Precision.HIGHEST)
    m = erased_shard
    while m.ndim < z.ndim:
        m = m[..., None]
    return jnp.where(m, 0.0, z)


def build_worker_products(mesh: Mesh):
    """The sharded worker-compute stage: ``(C, θ, erased) → z (N, ...)``.

    ``C`` sharded ``P("workers", None)``, ``θ`` replicated (``(k,)`` or a
    ``(k, dim)`` payload block), ``erased`` sharded ``P("workers")``; the
    output keeps the row sharding — the master's gather happens where the
    decode consumes it (XLA inserts the all-gather at the jit boundary's
    replicated consumer).
    """
    return shard_map(
        local_products, mesh=mesh,
        in_specs=(P("workers", None), P(), P("workers")),
        out_specs=P("workers"))


def local_products_seeded(idx_shard: jax.Array, coeff_shard: jax.Array,
                          M: jax.Array, theta: jax.Array,
                          erased_shard: jax.Array) -> jax.Array:
    """One worker shard's step with the encode FUSED into the matvec.

    Runs INSIDE ``shard_map``.  ``idx_shard``/``coeff_shard`` are this
    device's ``(rows/device, row_weight)`` generator gather tables —
    everything it ever stores about the code; ``M (k, k)`` and ``theta``
    are replicated.  Each device computes ``y = M θ`` locally (replicated
    math: identical bits everywhere, no communication) and gathers its
    rows of the codeword — the exact gather+sum
    :func:`repro.core.encoding.gather_encode` runs on a single device, so
    products are bit-identical to ``Scheme2.build_seeded``'s.
    """
    y = jnp.matmul(M, theta, precision=Precision.HIGHEST)
    z = gather_encode(idx_shard, coeff_shard, y)
    m = erased_shard
    while m.ndim < z.ndim:
        m = m[..., None]
    return jnp.where(m, 0.0, z)


def build_seeded_worker_products(mesh: Mesh):
    """The seeded sharded worker stage: ``(idx, coeff, M, θ, erased) → z``.

    Gather tables row-sharded ``P("workers", None)``; ``M``/``θ``
    replicated; ``erased`` sharded ``P("workers")``; output row-sharded
    like :func:`build_worker_products`'s.
    """
    return shard_map(
        local_products_seeded, mesh=mesh,
        in_specs=(P("workers", None), P("workers", None), P(), P(),
                  P("workers")),
        out_specs=P("workers"))


def build_seeded_fused_worker_products(code: LDPCCode, mesh: Mesh):
    """The FUSED seeded worker stage: ``(M, θ, erased) → z`` — no tables.

    Each device computes ``y = M θ`` (replicated math) and runs the fused
    Pallas encode kernel over ITS OWN codeword row window, regenerating the
    generator's (column, weight) pairs in-register from the code's seed:
    the per-device structure footprint drops from ``(N/W)·row_weight``
    table entries to the handful of seed ints baked into the program.  The
    row offset ``axis_index · rows_per_worker`` is a TRACED kernel operand,
    so all shards share one compilation.  Products are bit-identical to
    :func:`local_products_seeded`'s under jit (the kernel and the
    sequential :func:`repro.core.encoding.gather_encode` lower to the same
    FMA chain) — and therefore to ``Scheme2.build_seeded``'s.
    """
    from repro.kernels.ldpc_peel.ops import encode_seeded_fused_pallas

    st = generator_structure_of(code)
    n_workers = mesh.shape["workers"]
    if code.N % n_workers:
        raise ValueError(f"N={code.N} not divisible by {n_workers} workers")
    rows_per = code.N // n_workers

    def local_fused(M, theta, erased_shard):
        y = jnp.matmul(M, theta, precision=Precision.HIGHEST)
        row0 = jax.lax.axis_index("workers") * rows_per
        z = encode_seeded_fused_pallas(st, y, row0, n_out=rows_per)
        m = erased_shard
        while m.ndim < z.ndim:
            m = m[..., None]
        return jnp.where(m, 0.0, z)

    # check_vma=False: shard_map cannot infer pallas_call's varying axes;
    # the kernel only READS the replicated y, so the spec stays sound.
    return shard_map(
        local_fused, mesh=mesh,
        in_specs=(P(), P(), P("workers")),
        out_specs=P("workers"), check_vma=False)


def shard_generator_tables(code: LDPCCode, mesh: Mesh,
                           topology: WorkerTopology
                           ) -> tuple[jax.Array, jax.Array]:
    """Place a seeded code's generator gather tables row-sharded.

    ``(idx (N, row_weight) int32, coeff (N, row_weight) f32)`` with rows
    split over the workers axis — after this every device holds only its
    own workers' table rows (a real deployment would regenerate them from
    ``(seed, row)`` on arrival; here the host builds them once and shards).
    """
    topology.validate_mesh(mesh)
    idx, coeff = seeded_generator_rows(code, 0, code.N)
    sharding = row_sharding(mesh)
    return (jax.device_put(jnp.asarray(idx), sharding),
            jax.device_put(jnp.asarray(coeff), sharding))


def shard_encoded_rows(C: jax.Array, mesh: Mesh,
                       topology: WorkerTopology) -> jax.Array:
    """Place the encoded operator with rows split over the workers axis.

    Validates that worker shards do not straddle devices, then
    ``device_put``s ``C (N, k)`` with ``P("workers", None)`` — after this
    every device holds only its own workers' rows.
    """
    if C.shape[0] != topology.N:
        raise ValueError(f"C has {C.shape[0]} rows; topology expects "
                         f"{topology.N}")
    topology.validate_mesh(mesh)
    return jax.device_put(C, row_sharding(mesh))
