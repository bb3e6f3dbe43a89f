"""Attention variants for the zoo: GQA (qk-norm / QKV-bias / sliding-window)
and DeepSeek-style MLA (naive train path + absorbed-latent decode path).

The softmax attention core is chunked over query blocks (``lax.scan``) so the
(S x S) score matrix never materializes for a full sequence — the pure-JAX
analogue of flash attention (the Pallas kernel in kernels/flash_attention is
the TPU-tiled version of the same computation).

Caches (see repro.serving.kvcache) are dicts of preallocated arrays with a
ring-buffer variant for the sliding-window long-context decode shape.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense, dense_init, rmsnorm, rmsnorm_init

__all__ = [
    "sdpa_chunked", "init_gqa", "gqa_forward", "gqa_decode",
    "init_mla", "mla_forward", "mla_decode",
]

NEG_INF = -1e30


def sdpa_chunked(q, k, v, q_pos, kv_pos, *, causal=True, chunk=512,
                 kv_valid=None, unroll=False, remat=False) -> jax.Array:
    """Chunked scaled-dot-product attention.

    q: (B, Sq, KV, G, Dh) — query heads grouped per KV head (GQA).
    k, v: (B, T, KV, Dh).
    q_pos: (Sq,) absolute positions of queries; kv_pos: (T,) of keys.
    kv_valid: optional (T,) bool — e.g. ring-buffer slots actually filled.
    remat: recompute each chunk's scores in the backward pass instead of
    keeping them (a chunk's score matrix at a time, not the whole S x T).
    """
    B, Sq, KV, G, Dh = q.shape
    scale = 1.0 / math.sqrt(Dh)

    def block(qc, qp):
        s = jnp.einsum("bqkgd,btkd->bkgqt", qc.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        mask = jnp.ones(s.shape[-2:], bool)
        if causal:
            mask = qp[:, None] >= kv_pos[None, :]
        if kv_valid is not None:
            mask = mask & kv_valid[None, :]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32)).astype(v.dtype)

    if remat:
        block = jax.checkpoint(block)
    cq = min(chunk, Sq)
    if Sq % cq != 0 or Sq == cq:
        return block(q, q_pos)
    nc = Sq // cq
    qr = jnp.moveaxis(q.reshape(B, nc, cq, KV, G, Dh), 1, 0)
    qpr = q_pos.reshape(nc, cq)
    if unroll:
        # python loop: every chunk's flops visible to cost_analysis (the
        # dry-run path; lax.scan bodies are counted once by XLA's analysis)
        outs = jnp.stack([block(qr[i], qpr[i]) for i in range(nc)])
    else:
        _, outs = jax.lax.scan(lambda c, xs: (c, block(*xs)), None, (qr, qpr))
    Dv = v.shape[-1]  # may differ from Dh (MLA: v_head != qk dims)
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, KV, G, Dv)


# ---------------------------------------------------------------- GQA ------


def init_gqa(key, d_model: int, n_heads: int, n_kv: int, head_dim: int, *,
             qk_norm: bool = False, bias: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, bias=bias, dtype=dtype),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim, bias=bias, dtype=dtype),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim, bias=bias, dtype=dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, bias=False, dtype=dtype),
    }
    if qk_norm:
        p["q_norm"] = rmsnorm_init(head_dim, dtype)
        p["k_norm"] = rmsnorm_init(head_dim, dtype)
    return p


def _qkv(p, x, n_heads, n_kv, head_dim, positions, rope_theta, use_rope=True):
    B, S, _ = x.shape
    q = dense(p["wq"], x).reshape(B, S, n_heads, head_dim)
    k = dense(p["wk"], x).reshape(B, S, n_kv, head_dim)
    v = dense(p["wv"], x).reshape(B, S, n_kv, head_dim)
    if "q_norm" in p:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def gqa_forward(p, x, *, n_heads, n_kv, head_dim, positions, rope_theta=1e6,
                causal=True, chunk=512, cache=None, use_rope=True,
                kv_source=None, unroll=False):
    """Training / prefill / encoder attention over a full sequence.

    kv_source: if given (B, T, d) — cross-attention (keys/values from it,
    non-causal).  cache: if given, K/V are written into it (prefill).
    Returns (y, cache).
    """
    B, S, _ = x.shape
    G = n_heads // n_kv
    if kv_source is None:
        q, k, v = _qkv(p, x, n_heads, n_kv, head_dim, positions, rope_theta, use_rope)
        kv_pos = positions
    else:
        T = kv_source.shape[1]
        q = dense(p["wq"], x).reshape(B, S, n_heads, head_dim)
        k = dense(p["wk"], kv_source).reshape(B, T, n_kv, head_dim)
        v = dense(p["wv"], kv_source).reshape(B, T, n_kv, head_dim)
        kv_pos = jnp.arange(T)
        causal = False
    if cache is not None:
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        cache["pos"] = cache["pos"] * 0 + jnp.arange(cache["pos"].shape[0])
        cache["length"] = jnp.asarray(S, jnp.int32)
    o = sdpa_chunked(q.reshape(B, S, n_kv, G, head_dim), k, v, positions, kv_pos,
                     causal=causal, chunk=chunk, unroll=unroll)
    y = dense(p["wo"], o.reshape(B, S, n_heads * head_dim))
    return y, cache


def gqa_decode(p, x, *, n_heads, n_kv, head_dim, pos, cache, rope_theta=1e6,
               use_rope=True, cross=False):
    """Single-token decode. x: (B, 1, d); cache holds K/V (+ slot positions).

    Supports both a full cache (slot == pos) and a ring-buffer window cache
    (slot == pos % W, validity tracked via per-slot positions).
    cross: cross-attention decode — read-only cache of encoder K/V.
    """
    B = x.shape[0]
    q = dense(p["wq"], x).reshape(B, 1, n_heads, head_dim)
    if cross:
        if "q_norm" in p:
            q = rmsnorm(p["q_norm"], q)
        k, v = cache["k"], cache["v"]
        kv_valid = None
        kv_pos = cache["pos"]
        o = sdpa_chunked(q.reshape(B, 1, n_kv, n_heads // n_kv, head_dim), k, v,
                         jnp.full((1,), pos), kv_pos, causal=False)
        return dense(p["wo"], o.reshape(B, 1, n_heads * head_dim)), cache

    k = dense(p["wk"], x).reshape(B, 1, n_kv, head_dim)
    v = dense(p["wv"], x).reshape(B, 1, n_kv, head_dim)
    if "q_norm" in p:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if use_rope:
        posv = jnp.full((1,), pos)
        q = apply_rope(q, posv, rope_theta)
        k = apply_rope(k, posv, rope_theta)
    W = cache["k"].shape[1]
    slot = pos % W
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, slot, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, slot, 0, 0))
    cache["pos"] = cache["pos"].at[slot].set(pos)
    cache["length"] = jnp.maximum(cache["length"], pos + 1)
    kv_valid = cache["pos"] <= pos  # unfilled slots are initialized to INT_MAX
    o = sdpa_chunked(q.reshape(B, 1, n_kv, n_heads // n_kv, head_dim),
                     cache["k"], cache["v"], jnp.full((1,), pos), cache["pos"],
                     causal=True, kv_valid=kv_valid)
    return dense(p["wo"], o.reshape(B, 1, n_heads * head_dim)), cache


# ---------------------------------------------------------------- MLA ------


def init_mla(key, d_model: int, n_heads: int, *, kv_lora: int, q_lora: int = 0,
             qk_nope: int = 128, qk_rope: int = 64, v_head: int = 128,
             dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    qdim = n_heads * (qk_nope + qk_rope)
    p = {
        "w_dkv": dense_init(ks[0], d_model, kv_lora, dtype=dtype),
        "kv_norm": rmsnorm_init(kv_lora, dtype),
        "w_kr": dense_init(ks[1], d_model, qk_rope, dtype=dtype),
        "w_uk": (jax.random.normal(ks[2], (kv_lora, n_heads, qk_nope)) /
                 math.sqrt(kv_lora)).astype(dtype),
        "w_uv": (jax.random.normal(ks[3], (kv_lora, n_heads, v_head)) /
                 math.sqrt(kv_lora)).astype(dtype),
        "wo": dense_init(ks[4], n_heads * v_head, d_model, dtype=dtype),
    }
    if q_lora:
        p["w_dq"] = dense_init(ks[5], d_model, q_lora, dtype=dtype)
        p["q_norm"] = rmsnorm_init(q_lora, dtype)
        p["w_uq"] = dense_init(ks[6], q_lora, qdim, dtype=dtype)
    else:
        p["w_uq"] = dense_init(ks[6], d_model, qdim, dtype=dtype)
    return p


def _mla_q(p, x, n_heads, qk_nope, qk_rope, positions, rope_theta):
    B, S, _ = x.shape
    h = x
    if "w_dq" in p:
        h = rmsnorm(p["q_norm"], dense(p["w_dq"], x))
    q = dense(p["w_uq"], h).reshape(B, S, n_heads, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope, positions, rope_theta)
    return q_nope, q_rope


def mla_forward(p, x, *, n_heads, kv_lora, qk_nope=128, qk_rope=64, v_head=128,
                positions, rope_theta=1e6, chunk=512, cache=None, unroll=False,
                remat=False):
    """Naive (non-absorbed) MLA for training/prefill: materialize per-head K/V."""
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, n_heads, qk_nope, qk_rope, positions, rope_theta)
    c_kv = rmsnorm(p["kv_norm"], dense(p["w_dkv"], x))  # (B,S,kv_lora)
    k_rope = apply_rope(dense(p["w_kr"], x).reshape(B, S, 1, qk_rope), positions,
                        rope_theta)
    k_nope = jnp.einsum("bsl,lhd->bshd", c_kv, p["w_uk"])
    v = jnp.einsum("bsl,lhd->bshd", c_kv, p["w_uv"])
    if cache is not None:
        cache = dict(cache)
        cache["c_kv"] = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0))
        cache["k_rope"] = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope[:, :, 0, :].astype(cache["k_rope"].dtype), (0, 0, 0))
        cache["pos"] = cache["pos"] * 0 + jnp.arange(cache["pos"].shape[0])
        cache["length"] = jnp.asarray(S, jnp.int32)
    # fold rope part in as extra key dims: K = [k_nope ; k_rope broadcast]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, n_heads, qk_rope))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    # MLA has per-head K (no grouping): KV = n_heads, G = 1.
    o = sdpa_chunked(q_full[:, :, :, None, :], k_full, v, positions, positions,
                     causal=True, chunk=chunk, unroll=unroll, remat=remat)
    y = dense(p["wo"], o.reshape(B, S, n_heads * v_head))
    return y, cache


def mla_decode(p, x, *, n_heads, kv_lora, qk_nope=128, qk_rope=64, v_head=128,
               pos, cache, rope_theta=1e6):
    """Absorbed-latent MLA decode: attention runs in the kv_lora latent space,
    the cache holds only (c_kv, k_rope) — 576 dims/token for DeepSeek-V2
    instead of n_heads*(nope+v) = 32K dims. This is the paper-table MLA win."""
    B = x.shape[0]
    posv = jnp.full((1,), pos)
    q_nope, q_rope = _mla_q(p, x, n_heads, qk_nope, qk_rope, posv, rope_theta)
    c_kv_t = rmsnorm(p["kv_norm"], dense(p["w_dkv"], x))  # (B,1,kv_lora)
    k_rope_t = apply_rope(dense(p["w_kr"], x).reshape(B, 1, 1, qk_rope), posv,
                          rope_theta)[:, :, 0, :]
    W = cache["c_kv"].shape[1]
    slot = pos % W
    cache = dict(cache)
    cache["c_kv"] = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv_t.astype(cache["c_kv"].dtype), (0, slot, 0))
    cache["k_rope"] = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope_t.astype(cache["k_rope"].dtype), (0, slot, 0))
    cache["pos"] = cache["pos"].at[slot].set(pos)
    cache["length"] = jnp.maximum(cache["length"], pos + 1)
    kv_valid = cache["pos"] <= pos
    # absorb W_uk into the query: q_lat = q_nope @ W_uk  -> latent-space dot
    q_lat = jnp.einsum("bshd,lhd->bshl", q_nope, p["w_uk"])  # (B,1,H,kv_lora)
    scale = 1.0 / math.sqrt(qk_nope + qk_rope)
    s = (jnp.einsum("bshl,btl->bhst", q_lat.astype(jnp.float32),
                    cache["c_kv"].astype(jnp.float32))
         + jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                      cache["k_rope"].astype(jnp.float32))) * scale
    mask = (cache["pos"] <= pos) & kv_valid
    s = jnp.where(mask[None, None, None, :], s, NEG_INF)
    attn = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhst,btl->bshl", attn,
                         cache["c_kv"].astype(jnp.float32))  # (B,1,H,kv_lora)
    o = jnp.einsum("bshl,lhd->bshd", ctx_lat.astype(x.dtype), p["w_uv"])
    y = dense(p["wo"], o.reshape(B, 1, n_heads * v_head))
    return y, cache
