"""jit'd wrapper: GQA head expansion, padding, and (B, S, H, D) layout."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import detect_interpret
from repro.kernels.flash_attention.kernel import flash_call

__all__ = ["flash_attention"]


@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """q (B, Sq, H, D); k, v (B, Sk, KV, D) with H % KV == 0 (GQA).

    Returns (B, Sq, H, D).  Sq/Sk padded to tile multiples internally; the
    key-side padding is masked inside the kernel via seq_k.
    """
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    # (B, S, H, D) -> (B*H, S, D)
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * H, Sq if False else k.shape[1], D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], D)

    bq_eff = min(bq, max(8, Sq))
    bk_eff = min(bk, max(8, kh.shape[1]))
    pad_q = (-Sq) % bq_eff
    pad_k = (-kh.shape[1]) % bk_eff
    qp = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0)))
    out = flash_call(qp, kp, vp, bq=bq_eff, bk=bk_eff, causal=causal,
                     interpret=detect_interpret(interpret),
                     true_k=kh.shape[1])
    out = out[:, :Sq]
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
