"""Unified batched coded-compute engine: encode → erase → decode → epilogue.

The paper's pipeline — encode moments with an LDPC code, lose coordinates to
stragglers, peel-decode, zero-fill, update — used to be reimplemented in
every consumer (``Scheme2``/``Scheme2Blocked``, ``CodedAggregator``, the
launch-layer dry-run steps).  :class:`CodedComputeEngine` owns that pipeline
ONCE, as composable jit-able stages, and every consumer is a thin client:

======== ====================================================================
stage    what it does
======== ====================================================================
encode   ``symbols = G @ payload`` — systematic codeword(s) of the payload
         (the paper's offline moment encode, or per-step partial-gradient
         encode for coded aggregation).
erase    zero the straggled coordinates (workers that did not report).
decode   the peeling decode via :mod:`repro.core.decoder`'s backend matrix
         (dense / sparse neighbor-table / fused Pallas kernel — resident,
         check-axis tiled, or seed-regenerated "pallas_seeded"), fixed-D
         or adaptive early-exit.  The engine's ``code`` may be a
         structure-only :class:`repro.core.ldpc.SeededLDPC`: decode stages
         work unchanged (the seeded kernel needs no H), only ``encode``
         needs a materialized generator.
epilogue zero-fill the unresolved systematic coordinates (paper Scheme 2:
         both ``ĉ`` and ``b̂`` zeroed on the unresolved set keeps the
         gradient estimate an unbiased (1-q_D)-scaled gradient — Lemma 1).
======== ====================================================================

**The batch axis over independent erasure patterns is first-class**:
:meth:`CodedComputeEngine.decode_batch` (and :meth:`recover_batch`) run B
concurrent coded queries — each with its OWN straggler realization — in one
launch, via a vmapped sparse/dense flooding loop or the batched fused Pallas
kernel (grid over the batch, H resident in VMEM and shared).  The batch
axis carries PER-SLOT adaptive state (``adaptive=True`` / per-slot
``budgets``): every slot early-exits at its own fixpoint and reports its
own round count, so decoding effort tracks each query's realized straggler
load instead of the batch's worst case.  This is the primitive that serves
heavy concurrent coded traffic (:mod:`repro.serving.coded_queries`'s
continuous-admission slot server) and that every later scaling layer
(sharded decode, async serving, multi-code support) builds on.

The payload axis ``V`` (many codewords sharing ONE erasure pattern — the
paper's blocked Scheme 2, where one straggler erases the same coordinate of
every block) and the pattern axis ``B`` (many independent erasure patterns)
are orthogonal; the engine exposes both.
"""
from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
from jax.lax import Precision
import numpy as np

from repro.core.decoder import (
    SEEDED_MODES,
    DecodeResult,
    decode_layout,
    peel_decode,
    peel_decode_adaptive,
    peel_decode_batch,
    peel_decode_batch_adaptive,
    pick_tile_bp,
    resolve_backend,
    vmem_bytes_estimate,
)
from repro.core.ldpc import LDPCCode
from repro.obs import metrics as _obs_metrics

__all__ = ["CodedComputeEngine", "blocked_epilogue"]

logger = logging.getLogger(__name__)


def blocked_epilogue(values: jax.Array, erased: jax.Array, b: jax.Array,
                     *, K: int, nb: int) -> tuple[jax.Array, jax.Array]:
    """Blocked-Scheme-2 epilogue: zero-fill + re-interleave + moment shift.

    ``values (N, nb)`` / ``erased (N,)`` come out of a payload-batched
    decode of ``nb`` blocks sharing one erasure pattern; block ``i`` holds
    rows ``M[i*K:(i+1)*K]``, so flat coordinate ``j = i*K + r``.  Returns
    ``(g, unresolved_flat)`` with ``g = ĉ - b̂`` the (k,) approximate
    gradient (both ``ĉ`` and ``b̂`` zeroed on the unresolved set) and
    ``unresolved_flat`` its (k,) bool unresolved mask.

    Shared by :class:`repro.core.coded_step.Scheme2Blocked` and the sharded
    launch-layer step builder (:func:`repro.launch.steps.build_coded_gd_step`)
    so the epilogue exists exactly once.
    """
    unresolved = erased[:K]                              # same for all blocks
    c_hat = jnp.where(unresolved[:, None], 0.0, values[:K])   # (K, nb)
    c_flat = c_hat.T.reshape(-1)                         # (k,)
    unresolved_flat = jnp.tile(unresolved, nb)
    b_hat = jnp.where(unresolved_flat, 0.0, b)
    return c_flat - b_hat, unresolved_flat


@dataclasses.dataclass(frozen=True)
class CodedComputeEngine:
    """One code + one decode policy, applied as composable pipeline stages.

    Construction is cheap (stores references); schemes build one per call
    site without jit-cache churn — the jitted stage functions are keyed on
    array shapes and the (static) backend/iteration knobs, not on engine
    identity.
    """

    code: LDPCCode
    decode_iters: int = 10
    # dense | sparse | pallas | pallas_tiled | pallas_seeded | replay | auto
    backend: str = "auto"
    adaptive: bool = False
    # backend="replay" only: the cross-pattern LRU of compiled peeling
    # schedules (repro.core.schedule_cache.ScheduleCache).  With a cache,
    # recurring straggler patterns pay the symbolic solve once and every
    # later decode is pure replay; without one the decode entry points
    # solve per call.  Replay dispatch needs CONCRETE erasure masks (the
    # schedule is a function of the pattern) — eager engine calls qualify,
    # jitted callers must pre-solve at dispatch time instead.
    schedule_cache: object | None = None
    # Tile plumbing for the check-axis-tiled fused kernels: bp (check-tile
    # height; None = sized from the VMEM budget) and bv (payload tile), plus
    # the VMEM budget "auto" dispatches on (None = decoder default, 8 MiB).
    bp: int | None = None
    bv: int | None = None
    vmem_budget_bytes: int | None = None
    # "pallas_seeded" round sub-dispatch: dense_tile | gather | auto
    # (the hwcaps FLOPs-crossover rule); ignored by other backends.
    seeded_mode: str = "dense_tile"
    # The materialized parity-check matrix as a RUNTIME operand for the
    # dense / pallas / pallas_tiled backends (None = code.H).  Jitted
    # callers hand it in (``dataclasses.replace(engine, H=H)`` inside the
    # traced function) so the (p, N) matrix is an argument of their
    # program, not a constant compiled into it.
    H: jax.Array | None = dataclasses.field(default=None, compare=False,
                                            repr=False)

    def __post_init__(self) -> None:
        # Fail fast on unknown/unsupported backend names (same matrix as
        # decoder.resolve_backend) instead of at first decode, and record
        # the resolved dispatch where operators can see it.
        resolve_backend(self.backend, self.code, adaptive=self.adaptive,
                        vmem_budget_bytes=self.vmem_budget_bytes)
        if self.seeded_mode not in SEEDED_MODES:
            raise ValueError(f"unknown seeded_mode {self.seeded_mode!r}; "
                             f"want one of {SEEDED_MODES}")
        reg = _obs_metrics.active()
        if reg is not None:
            # The dispatch decision, discoverable at runtime: the full
            # debug_info() dict lands in the registry snapshot (one info
            # series per distinct resolved config), not just a DEBUG log
            # line that is lost unless logging was pre-configured.
            info = self.debug_info()
            reg.counter("engine.built_total", backend=self.backend,
                        resolved=info["resolved_backend"]).inc()
            reg.info("engine.dispatch", info, backend=self.backend,
                     resolved=info["resolved_backend"], N=self.code.N)
        if logger.isEnabledFor(logging.DEBUG):
            logger.debug("CodedComputeEngine: %s", self.debug_info())

    def debug_info(self) -> dict:
        """The engine's decode dispatch, resolved: requested vs chosen
        backend, the VMEM working-set estimate the choice was made on, and
        the concrete tile knobs the tiled kernels would run with."""
        resolved = resolve_backend(self.backend, self.code,
                                   adaptive=self.adaptive,
                                   vmem_budget_bytes=self.vmem_budget_bytes)
        return {
            "backend": self.backend,
            "resolved_backend": resolved,
            "vmem_bytes_estimate": vmem_bytes_estimate(self.code),
            "vmem_budget_bytes": self.vmem_budget_bytes,
            "bp": (self.bp if self.bp is not None else pick_tile_bp(
                self.code, vmem_budget_bytes=self.vmem_budget_bytes)),
            "bv": self.bv if self.bv is not None else 128,
            "N": self.code.N,
            "decode_iters": self.decode_iters,
            "adaptive": self.adaptive,
            "seeded_mode": self.seeded_mode,
            "schedule_cache_capacity": (
                None if self.schedule_cache is None
                else getattr(self.schedule_cache, "capacity", None)),
        }

    def _tile_kw(self) -> dict:
        return {"bp": self.bp, "bv": self.bv,
                "vmem_budget_bytes": self.vmem_budget_bytes,
                "seeded_mode": self.seeded_mode, "H": self.H}

    def _schedule_kw(self, erased, *, batch: bool) -> dict:
        """``schedule=``/``schedules=`` operands for replay dispatch, from
        the engine's cache.  Only consulted for ``backend="replay"`` with a
        concrete mask — under jit the mask is a tracer and the decoder's
        own error message points the caller at pre-solving."""
        if (self.backend != "replay" or self.schedule_cache is None
                or isinstance(erased, jax.core.Tracer)):
            return {}
        if batch:
            return {"schedules": self.schedule_cache.get_batch(self.code,
                                                               erased)}
        return {"schedule": self.schedule_cache.get(self.code, erased)}

    def _record_decode(self, dec: DecodeResult) -> DecodeResult:
        """Feed eager decode outcomes into the obs registry.

        Strictly a host-side side channel: under jit/vmap the results are
        tracers and recording is skipped entirely (no new traced operands,
        no cache-key changes — the jitted consumers stay bit-identical).
        Eager callers pay one host fetch of the tiny stats arrays.
        """
        reg = _obs_metrics.active()
        if reg is None or isinstance(dec.erased, jax.core.Tracer):
            return dec
        rounds = np.atleast_1d(np.asarray(dec.rounds_used))
        erased = np.asarray(dec.erased)
        unres = (erased.sum(axis=-1) if erased.ndim > 1
                 else np.atleast_1d(erased.sum()))
        reg.histogram("engine.decode.rounds", bins=_obs_metrics.ROUND_BINS,
                      backend=self.backend).observe_many(rounds)
        reg.histogram("engine.decode.unresolved",
                      bins=_obs_metrics.COUNT_BINS,
                      backend=self.backend).observe_many(unres)
        return dec

    # -------------------------------------------------------------- stages

    @property
    def N(self) -> int:
        return self.code.N

    @property
    def K(self) -> int:
        return self.code.K

    def encode(self, payload: jax.Array) -> jax.Array:
        """(K, ...) systematic payload → (N, ...) worker symbols (G @ m)."""
        G = jnp.asarray(self.code.G, payload.dtype)
        return jnp.matmul(G, payload, precision=Precision.HIGHEST)

    @staticmethod
    def erase(symbols: jax.Array, mask: jax.Array) -> jax.Array:
        """Zero the straggled coordinates.  ``mask`` broadcasts from the
        right-aligned coordinate axis: (N,) against (N,), (N, V), or the
        batched (B, N) against (B, N), (B, N, V)."""
        m = mask
        while m.ndim < symbols.ndim:
            m = m[..., None]
        return jnp.where(m, 0.0, symbols)

    def decode(self, values: jax.Array, erased: jax.Array) -> DecodeResult:
        """One erasure pattern; values (N,) or (N, V) (payload axis)."""
        kw = {**self._tile_kw(), **self._schedule_kw(erased, batch=False)}
        if self.adaptive:
            # decode_iters doubles as the adaptive round budget (max_iters),
            # matching the pre-engine Scheme2 semantics.
            return self._record_decode(peel_decode_adaptive(
                self.code, values, erased, self.decode_iters,
                backend=self.backend, **kw))
        return self._record_decode(peel_decode(
            self.code, values, erased, self.decode_iters,
            backend=self.backend, **kw))

    def decode_batch(self, values: jax.Array, erased: jax.Array, *,
                     adaptive: bool | None = None,
                     budgets: jax.Array | None = None) -> DecodeResult:
        """B independent erasure patterns in ONE launch; values (B, N) or
        (B, N, V), erased (B, N).  Each slot decodes exactly as
        :meth:`decode` would decode it alone.

        ``adaptive`` overrides the engine's policy for this call (``None``
        = engine default).  Adaptive batches run the PER-SLOT early-exit
        decode (:func:`repro.core.decoder.peel_decode_batch_adaptive`): each
        slot stops at its own fixpoint under ``decode_iters`` (or its entry
        in ``budgets``, a traced per-slot round-budget vector), and
        ``rounds_used`` comes back as the per-slot ``(B,)`` stats vector —
        per-slot unresolved counts are ``result.erased.sum(axis=1)``.
        ``budgets`` is only meaningful for adaptive decodes."""
        use_adaptive = self.adaptive if adaptive is None else adaptive
        kw = {**self._tile_kw(), **self._schedule_kw(erased, batch=True)}
        if use_adaptive:
            return self._record_decode(peel_decode_batch_adaptive(
                self.code, values, erased, self.decode_iters,
                backend=self.backend, budgets=budgets, **kw))
        if budgets is not None:
            raise ValueError(
                "budgets= requires the adaptive batched decode (engine "
                "adaptive=True or decode_batch(adaptive=True)); the fixed-D "
                "path would silently ignore the per-slot round budgets")
        return self._record_decode(peel_decode_batch(
            self.code, values, erased, self.decode_iters,
            backend=self.backend, **kw))

    def systematic(self, dec: DecodeResult) -> tuple[jax.Array, jax.Array]:
        """Epilogue: zero-filled systematic part + its unresolved mask.

        Handles both single (values (N,)/(N,V)) and batched
        (values (B,N)/(B,N,V)) decode results; the systematic slice is the
        first K coordinates of the coordinate axis.
        """
        K = self.code.K
        batched = dec.erased.ndim == 2
        ax = 1 if batched else 0
        vals = jax.lax.slice_in_dim(dec.values, 0, K, axis=ax)
        unresolved = jax.lax.slice_in_dim(dec.erased, 0, K, axis=ax)
        m = unresolved
        while m.ndim < vals.ndim:
            m = m[..., None]
        return jnp.where(m, 0.0, vals), unresolved

    # ------------------------------------------------------- composed steps

    def recover(self, symbols: jax.Array, mask: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
        """erase → decode → epilogue for one pattern: returns the
        zero-filled systematic (K, ...) values and the (K,) unresolved mask.
        The symbol-major decode zeroes the erased rows itself and reads no
        value there, so on that layout the erase is left out."""
        if not self._decode_erases(symbols):
            symbols = self.erase(symbols, mask)
        return self.systematic(self.decode(symbols, mask))

    def _decode_erases(self, symbols: jax.Array) -> bool:
        if self.adaptive or symbols.ndim != 2:
            return False
        backend = resolve_backend(self.backend, self.code,
                                  vmem_budget_bytes=self.vmem_budget_bytes)
        return (decode_layout(backend, symbols.shape[1])
                == "symbol_major")

    def recover_batch(self, symbols: jax.Array, mask: jax.Array, *,
                      adaptive: bool | None = None,
                      budgets: jax.Array | None = None
                      ) -> tuple[jax.Array, jax.Array]:
        """erase → decode → epilogue for B patterns in one launch: returns
        (B, K, ...) zero-filled systematic values and (B, K) unresolved.
        ``adaptive`` / ``budgets`` pass through to :meth:`decode_batch`
        (per-slot early exit and round budgets)."""
        dec = self.decode_batch(self.erase(symbols, mask), mask,
                                adaptive=adaptive, budgets=budgets)
        return self.systematic(dec)
