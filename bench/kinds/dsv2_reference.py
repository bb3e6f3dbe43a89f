"""Plain reference of a DeepSeek-V2 decoder's training step, and of the
coded sum of its shard gradients: the benchmark's own copy, which imports
nothing of the program (the program's copy is
``src/repro/models/dsv2_reference.py``; ``bench/tests`` holds them to each
other), with the plain AdamW step the control applies.

Straightforward ``jax.numpy`` in float32, under
``jax.default_matmul_precision("highest")``: no kernel, no scan, no cache,
no capacity buffer.  It reads the parameters in the layout
:class:`repro.models.Model` gives them (``prefix`` layers, then the stacked
``blocks``) and follows the published description (arXiv:2405.04434 §2):

* RMSNorm before attention and before the feed-forward, residual adds;
* multi-head latent attention without query compression: ``q = W_uq h``
  split into a no-position part and a rotary part; the keys and values come
  from the latent ``c = RMSNorm(W_dkv h)`` through ``W_uk`` and ``W_uv``;
  one rotary key ``rope(W_kr h)`` is shared by all heads (the decoupled
  RoPE key); scores over ``sqrt(qk_nope + qk_rope)``, causal softmax;
* a dense SwiGLU feed-forward in the leading layers, then a mixture of
  experts: softmax router over all ``n_experts``, greedy top-k, gates as
  the softmax gives them (``norm_topk_prob`` false; renormalised where
  true), each routed expert and the shared experts a SwiGLU MLP;
* untied output projection, mean next-token cross entropy.

Departures, each shared with the program:

* rotary embedding without YaRN scaling (the 4K pre-training stage); the
  rotary pairs are the two halves of the rotary dims (a fixed relabelling
  of the dims, which random weights cannot tell apart);
* the Switch-style batch auxiliary loss ``E·Σ_e f_e·P_e`` over all
  assignments, weighted ``aux_coef / n_layers``, in place of DeepSeek-V2's
  sequence-wise one;
* token dropping at a capacity of ``capacity`` assignments per expert: an
  assignment (token t, choice j) is kept where fewer than ``capacity``
  assignments to its expert come before it in (t, j) order;
* one chip's share of each MoE layer: the router routes over all
  ``n_experts``, and only the held experts ``[expert_offset, expert_offset
  + held)`` contribute (the rest lie on other chips of the deployment).

``precision`` is the reference's own (``"highest"``) or the control's
(``"fp8"``: every matmul operand rounded to a 3-bit mantissa, the 8-bit
float's, with float32's exponent range, as per-tensor scaling would give).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["shard_loss", "shard_grad", "coded_grad", "peel_erased",
           "layers_of", "adamw"]


# attention heads whose score matrices are held at once
_HEADS_AT_ONCE = 4


def _round(x, precision: str):
    if precision == "fp8":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    if precision != "highest":
        raise ValueError(f"no precision {precision!r}")
    return x


def _mm(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision))


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta: float):
    """``x (S, H, D)``: rotary embedding at positions 0..S-1."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv    # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(p, h, precision):
    g = _mm("sd,df->sf", h, p["gate"]["w"], precision)
    u = _mm("sd,df->sf", h, p["up"]["w"], precision)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, p["down"]["w"], precision)


def _mla(p, h, cfg, precision):
    S = h.shape[0]
    H, dn, dr, dv = (cfg["n_heads"], cfg["qk_nope"], cfg["qk_rope"],
                     cfg["v_head"])
    q = _mm("sd,de->se", h, p["w_uq"]["w"], precision).reshape(S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])
    c = _rmsnorm(_mm("sd,dl->sl", h, p["w_dkv"]["w"], precision),
                 p["kv_norm"]["scale"], cfg["norm_eps"])
    k_rope = _rope(_mm("sd,dr->sr", h, p["w_kr"]["w"], precision)[:, None],
                   cfg["rope_theta"])                          # (S, 1, dr)
    k_nope = _mm("sl,lhd->shd", c, p["w_uk"], precision)
    v = _mm("sl,lhd->shd", c, p["w_uv"], precision)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def heads(qn, qr, kn, vh):
        s = (_mm("qhd,khd->hqk", qn, kn, precision)
             + _mm("qhd,kd->hqk", qr, k_rope[:, 0], precision))
        s = s / math.sqrt(dn + dr)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", a, vh, precision)

    # a few heads at a time, each group's S x S scores recomputed in the
    # backward pass: the same sums, one group's scores held at once
    g = min(H, _HEADS_AT_ONCE)
    o = jnp.concatenate([jax.checkpoint(heads)(
        q_nope[:, h:h + g], q_rope[:, h:h + g], k_nope[:, h:h + g],
        v[:, h:h + g]) for h in range(0, H, g)], axis=1)
    return _mm("se,ed->sd", o.reshape(S, H * dv), p["wo"]["w"], precision)


def _moe(p, h, cfg, precision):
    """``(y, aux)``: the held experts' part plus the shared experts."""
    S = h.shape[0]
    E, k, C = cfg["n_experts"], cfg["top_k"], cfg["capacity"]
    probs = jax.nn.softmax(_mm("sd,de->se", h, p["router"]["w"], precision),
                           axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                       # (S, k)
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    onehot = jax.nn.one_hot(idx.reshape(S * k), E, dtype=jnp.int32)
    before = jnp.cumsum(onehot, axis=0) - onehot               # (S*k, E)
    kept = ((before * onehot).sum(-1) < C).reshape(S, k)
    ex = p["experts"]
    held = ex["down"]["w"].shape[0]
    mine = cfg["expert_offset"] + jnp.arange(held)               # (held,)
    w = jnp.sum(jnp.where((idx[:, None, :] == mine[None, :, None])
                          & kept[:, None, :], gates[:, None, :], 0.0),
                axis=-1)                                         # (S, held)
    # every held expert on every token, weighted by its gate (zero where
    # the token was not routed there or its assignment was dropped)
    g = _mm("sd,edf->esf", h, ex["gate"]["w"], precision)
    u = _mm("sd,edf->esf", h, ex["up"]["w"], precision)
    out = _mm("esf,efd->esd", jax.nn.silu(g) * u, ex["down"]["w"], precision)
    y = jnp.einsum("se,esd->sd", w, out)
    if "shared" in p:
        y = y + _swiglu(p["shared"], h, precision)
    f = onehot.sum(0).astype(jnp.float32) / (S * k)
    aux = E * jnp.sum(f * probs.mean(axis=0))
    return y, aux


def layers_of(params) -> list:
    """Per-layer parameter dicts in order: the prefix layers, then each
    block of the stacked body, position by position."""
    out = [params["prefix"][str(i)] for i in range(len(params["prefix"]))]
    body = params["blocks"]
    if body:
        n = jax.tree.leaves(body)[0].shape[0]
        out += [jax.tree.map(lambda a: a[b], body[f"sub{j}"])
                for b in range(n) for j in range(len(body))]
    return out


def _layer(p, x, cfg, precision):
    h = _rmsnorm(x, p["ln1"]["scale"], cfg["norm_eps"])
    x = x + _mla(p["mixer"], h, cfg, precision)
    h = _rmsnorm(x, p["ln2"]["scale"], cfg["norm_eps"])
    if "router" in p["ffn"]:
        y, aux = _moe(p["ffn"], h, cfg, precision)
    else:
        y, aux = _swiglu(p["ffn"], h, precision), jnp.zeros((), jnp.float32)
    return x + y, aux


def shard_loss(params, tokens, labels, cfg: dict, precision: str = "highest"):
    """One shard's loss: ``tokens``, ``labels`` ``(S,)``."""
    x = params["embed"]["table"][tokens]
    aux = jnp.zeros((), jnp.float32)
    layers = layers_of(params)
    for p in layers:
        # each layer's activations are recomputed in the backward pass, so
        # that only one layer's score matrices are held at a time
        x, a = jax.checkpoint(partial(_layer, cfg=cfg,
                                      precision=precision))(p, x)
        aux = aux + a
    x = _rmsnorm(x, params["final_norm"]["scale"], cfg["norm_eps"])
    logits = _mm("sd,vd->sv", x, params["unembed"]["w"], precision)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0]
    return nll.mean() + cfg["aux_coef"] * aux / len(layers)


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _loss_and_grad(params, tokens, labels, cfg_items, precision):
    return jax.value_and_grad(shard_loss)(params, tokens, labels,
                                          dict(cfg_items), precision)


def shard_grad(params, tokens, labels, cfg: dict,
               precision: str = "highest"):
    """``(loss, gradient)`` of one shard, float32 at the highest matmul
    precision (the control's operands rounded as ``precision`` says)."""
    with jax.default_matmul_precision("highest"):
        return _loss_and_grad(params, tokens, labels,
                              tuple(sorted(cfg.items())), precision)


def peel_erased(h_cols: np.ndarray, erased: np.ndarray,
                rounds: int) -> np.ndarray:
    """Erasures left after ``rounds`` flooding rounds of peeling: in each
    round every check with exactly one erased neighbour resolves it, all
    checks at once.  ``h_cols (p, r)`` each check's neighbour columns."""
    e = np.array(erased, bool)
    for _ in range(rounds):
        ne = e[h_cols]
        one = ne.sum(axis=1) == 1
        if not one.any():
            break
        e[h_cols[one][ne[one]]] = False
    return e


def coded_grad(params, tokens, labels, resolved, cfg: dict,
               precision: str = "highest"):
    """``(mean shard loss, Σ_{resolved i} g_i / K)`` over the K shards of
    ``tokens``/``labels (K, S)``, one shard at a time."""
    K = tokens.shape[0]
    total, losses = None, []
    for i in range(K):
        loss, g = shard_grad(params, tokens[i], labels[i], cfg, precision)
        losses.append(loss)
        if not resolved[i]:
            continue
        g = jax.tree.map(lambda a: a / K, g)
        total = g if total is None else jax.tree.map(jnp.add, total, g)
    if total is None:
        total = jax.tree.map(jnp.zeros_like, params)
    return jnp.mean(jnp.stack(losses)), total


@partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"))
def adamw(params, grads, m, v, step, *, lr: float, b1: float = 0.9,
          b2: float = 0.95, eps: float = 1e-8, wd: float = 0.01):
    """One AdamW step (``step`` counts from 1): ``(params, m, v)``."""
    t = step.astype(jnp.float32)

    def upd(p, g, m_, v_):
        m2 = b1 * m_ + (1.0 - b1) * g
        v2 = b2 * v_ + (1.0 - b2) * g * g
        u = (m2 / (1.0 - b1 ** t)) / (jnp.sqrt(v2 / (1.0 - b2 ** t)) + eps)
        return p - lr * (u + wd * p), m2, v2

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)
