"""Beyond-paper: LDPC-coded gradient aggregation for arbitrary additive losses.

The paper's moment encoding is specific to squared loss (only there does the
gradient factor through a fixed matrix ``M = X^T X``).  The transferable
insight — *add sparse linear redundancy across workers' partial results and
peel-decode erasures at the aggregator* — applies to ANY loss of the form
``L(θ) = Σ_i ℓ_i(θ)``, including every architecture in the model zoo:

* the data is split into ``K`` shards; shard ``i``'s partial gradient
  ``g_i`` (flattened) is the ``i``-th *systematic* symbol;
* ``p`` parity workers each hold the union of ``r-1`` shards (LDGM rows must
  be sparse so a parity worker's data footprint stays small — this is why
  :func:`repro.core.ldpc.make_ldgm` exists) and return the weighted sum
  ``c_j = Σ_i P[j,i] g_i``;
* the master peels for ``D`` rounds; unresolved systematic symbols are
  zero-filled.  Lemma 1's argument carries verbatim: under Bernoulli(q0)
  straggling the aggregate is an unbiased ``(1 - q_D)``-scaled gradient.

On a TPU mesh the "workers" are data-parallel shards and this substitutes
the plain gradient all-reduce; see launch/train.py's ``--coded-agg`` flag.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core.engine import CodedComputeEngine
from repro.core.ldpc import LDPCCode, make_ldgm

__all__ = ["CodedAggregator", "BucketLayout", "DDP_BUCKET_ELEMS"]

# PyTorch DDP's default bucket_cap_mb (25 MiB of float32)
DDP_BUCKET_ELEMS = 25 * 2 ** 20 // 4


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """A parameter group's gradient cut into equal buckets of ``cap``
    float32 (the last one zero-padded), so that the coded reduce runs one
    bucket at a time, as data-parallel training reduces its buckets during
    the backward pass, and never holds the whole model K shards deep.

    ``cap`` is at most ``max_cap`` and at least 512 (a multiple of 512): a
    group smaller than ``max_cap`` is one bucket of its own size.
    """

    treedef: object
    shapes: tuple
    cap: int

    @classmethod
    def of(cls, tree, max_cap: int = DDP_BUCKET_ELEMS) -> "BucketLayout":
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        shapes = tuple(tuple(l.shape) for l in leaves)
        size = sum(math.prod(sh) for sh in shapes)
        cap = min(max_cap, size)
        return cls(treedef, shapes, -(-cap // 512) * 512)

    @property
    def size(self) -> int:
        return sum(math.prod(sh) for sh in self.shapes)

    @property
    def n_buckets(self) -> int:
        return -(-self.size // self.cap)

    def pack(self, tree) -> tuple:
        """One gradient tree -> ``n_buckets`` float32 ``(cap,)`` buckets."""
        leaves = self.treedef.flatten_up_to(tree)
        flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                                for l in leaves])
        flat = jnp.pad(flat, (0, self.n_buckets * self.cap - self.size))
        return tuple(flat.reshape(self.n_buckets, self.cap))

    def unpack(self, buckets):
        """``n_buckets`` ``(cap,)`` sums -> the gradient tree."""
        flat = jnp.concatenate(list(buckets))
        out, off = [], 0
        for sh in self.shapes:
            n = math.prod(sh)
            out.append(flat[off:off + n].reshape(sh))
            off += n
        return jax.tree_util.tree_unflatten(self.treedef, out)


@dataclasses.dataclass(frozen=True)
class CodedAggregator:
    """LDPC(-LDGM)-coded sum of K partial gradients with straggler erasures.

    ``aggregate(partials, mask, iters)``: ``partials`` is (K, dim) — the
    systematic symbols.  Parity symbols are formed *as the parity workers
    would* (sparse combos of the shards each parity worker owns), then the
    straggler mask erases worker symbols and the master peels.  Returns the
    zero-filled sum ``Σ_i ĝ_i`` and the number of unresolved shards.
    """

    code: LDPCCode
    decode_iters: int = 8
    decode_backend: str = "auto"  # dense | sparse | pallas | auto (decoder.py)
    debias_scale: float = 1.0  # optional 1/(1-q_D) correction

    @classmethod
    def build(cls, n_shards: int, *, redundancy: float = 0.5, row_weight: int = 4,
              seed: int = 0, **kw) -> "CodedAggregator":
        p = max(1, int(round(n_shards * redundancy)))
        return cls(code=make_ldgm(n_shards, p, row_weight=row_weight, seed=seed), **kw)

    @property
    def n_workers(self) -> int:
        return self.code.N

    @property
    def n_shards(self) -> int:
        return self.code.K

    @property
    def engine(self) -> CodedComputeEngine:
        return CodedComputeEngine(self.code, decode_iters=self.decode_iters,
                                  backend=self.decode_backend)

    def encode(self, partials: jax.Array) -> jax.Array:
        """(K, dim) systematic partial gradients -> (N, dim) worker symbols."""
        return self.engine.encode(partials)

    def aggregate(self, partials: jax.Array, straggler_mask: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
        # The full engine pipeline: encode → erase → decode → zero-fill.
        recovered, unresolved = self.engine.recover(
            self.encode(partials), straggler_mask)
        total = recovered.sum(axis=0) * self.debias_scale
        return total, unresolved.sum()
