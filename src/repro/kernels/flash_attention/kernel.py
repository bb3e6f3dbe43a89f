"""Causal flash attention (online softmax) as a Pallas TPU kernel.

Grid: (batch*heads, Sq/bq).  Each program owns one (bq, d) query tile in
VMEM and streams (bk, d) key/value tiles with a ``fori_loop``, maintaining
the running max ``m``, normalizer ``l`` and accumulator ``acc`` — the
standard flash-attention recurrence, f32 throughout.

Causality is exploited structurally: query tile ``i`` only loops over KV
tiles up to ``ceil((i+1)*bq / bk)`` — the remaining tiles are never read
from VMEM (and on real TPU never DMA'd).

This kernel is the TPU-tiled version of models/attention.sdpa_chunked and is
cross-checked against it (and a naive softmax oracle) in the test sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import detect_interpret

__all__ = ["flash_call"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bk, seq_k, true_k,
                  causal, scale):
    i = pl.program_id(1)
    q = q_ref[...][0].astype(jnp.float32)  # (bq, d)
    d = q.shape[-1]
    q_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    n_kv = seq_k // bk
    if causal:
        # last kv tile that intersects the causal triangle of this q tile
        upper = jnp.minimum(n_kv, (i * bq + bq + bk - 1) // bk)
    else:
        upper = n_kv

    def body(kb, carry):
        acc, m, l = carry
        k = k_ref[0, pl.dslice(kb * bk, bk), :].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, pl.dslice(kb * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot(q, k.T, precision=jax.lax.Precision.HIGHEST) * scale
        kv_idx = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kv_idx < true_k  # key-side padding masked out
        if causal:
            mask = mask & (q_idx >= kv_idx)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot(
            p, v, precision=jax.lax.Precision.HIGHEST)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out[None].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "causal", "interpret", "true_k"))
def flash_call(q: jax.Array, k: jax.Array, v: jax.Array, *, bq: int = 128,
               bk: int = 128, causal: bool = True,
               interpret: bool | None = None,
               true_k: int | None = None):
    """q (BH, Sq, d), k/v (BH, Sk, d) — padded to tile multiples by ops.py.
    true_k: un-padded key length (padding keys are masked)."""
    BH, Sq, d = q.shape
    Sk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_flash_kernel, bq=bq, bk=bk, seq_k=Sk,
                             true_k=true_k if true_k is not None else Sk,
                             causal=causal, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(BH, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        interpret=detect_interpret(interpret),
    )(q, k, v)
