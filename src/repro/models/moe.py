"""Mixture-of-Experts with capacity-based top-k token dispatch.

Dispatch is gather/scatter based (argsort packing), never a one-hot
(T, E, C) tensor — at DeepSeek-V2/Kimi-K2 scale the one-hot would be
terabytes.  Under the production mesh the expert dimension is sharded over
"model" and the capacity dimension over "data", so the dispatch gathers
lower to the expert-parallel all-to-all-style collectives on TPU.

A standard auxiliary load-balance loss (Switch/DeepSeek style) is returned
alongside the outputs.

Expert parallelism: a layer whose ``experts`` stack holds fewer experts than
``n_experts`` is one chip's share.  It routes over all ``n_experts`` (the
router keeps its full width and top-k), and computes the part of the result
its own experts ``[expert_offset, expert_offset + held)`` give, with the
same capacity rule as the whole layer; shared experts are whole on every
share.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, mlp, mlp_init

__all__ = ["init_moe", "moe_forward", "capacity_for"]


def capacity_for(n_tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float = 1.25, multiple: int = 8) -> int:
    c = int(n_tokens * top_k * capacity_factor / n_experts) + 1
    return max(multiple, c + (-c) % multiple)


def init_moe(key, d: int, d_ff_expert: int, n_experts: int, *, n_shared: int = 0,
             act: str = "swiglu", dtype=jnp.float32, held: int = 0,
             expert_offset: int = 0):
    """Router over ``n_experts``; the experts ``[expert_offset,
    expert_offset + held)`` (all of them where ``held`` is 0), each drawn as
    the whole layer would draw it."""
    ks = jax.random.split(key, 5)
    held = held or n_experts

    def stack_init(k):
        keys = jax.random.split(k, n_experts)[expert_offset:expert_offset + held]
        return jax.vmap(lambda kk: mlp_init(kk, d, d_ff_expert, act=act, dtype=dtype))(keys)

    p = {
        "router": dense_init(ks[0], d, n_experts, dtype=jnp.float32),
        "experts": stack_init(ks[1]),
    }
    if n_shared:
        p["shared"] = mlp_init(ks[2], d, n_shared * d_ff_expert, act=act, dtype=dtype)
    return p


def _expert_mlp(experts, xe, act: str):
    """xe: (E, C, d) -> (E, C, d) via per-expert MLP (batched einsum)."""
    if act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", xe, experts["gate"]["w"])
        u = jnp.einsum("ecd,edf->ecf", xe, experts["up"]["w"])
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, experts["up"]["w"]))
    return jnp.einsum("ecf,efd->ecd", h, experts["down"]["w"])


def moe_forward(p, x, *, n_experts: int, top_k: int, capacity_factor: float = 1.25,
                act: str = "swiglu", router_noise: float = 0.0, key=None,
                groups: int = 1, norm_topk_prob: bool = True,
                expert_offset: int = 0):
    """x: (B, S, d) -> (y, aux_loss, dropped), ``dropped`` the number of
    this share's routed assignments that its capacity dropped.

    Tokens are routed to their top-k experts; each expert processes at most C
    tokens (overflow dropped — standard capacity-based MoE).  The top-k gate
    values are renormalised to sum to one unless ``norm_topk_prob`` is
    False (DeepSeek-V2: the softmax probabilities are used as they are).

    groups > 1 (§Perf H2): routing/dispatch/combine run independently per
    token group, with the group dim aligned to the data-parallel batch
    sharding.  Every (assignments x d) gather/scatter then carries a
    data-sharded leading dim instead of living in the global token space, so
    the SPMD partitioner emits per-shard transfers instead of all-reducing
    the full combine matrix across the mesh (measured 137 GB/chip -> per-
    shard GBs on jamba prefill_32k).  groups must divide B; capacity is per
    group, so routing quality is per-shard (standard EP semantics).
    """
    B, S, d = x.shape
    kw = dict(n_experts=n_experts, top_k=top_k,
              capacity_factor=capacity_factor, act=act,
              norm_topk_prob=norm_topk_prob, expert_offset=expert_offset)
    if groups > 1 and B % groups == 0:
        xg = x.reshape(groups, (B // groups) * S, d)
        out, aux, dropped = jax.vmap(lambda xt: _moe_tokens(p, xt, **kw))(xg)
        out, aux, dropped = out.reshape(B, S, d), aux.mean(), dropped.sum()
    else:
        out, aux, dropped = _moe_tokens(p, x.reshape(B * S, d), key=key,
                                        router_noise=router_noise, **kw)
        out = out.reshape(B, S, d)
    return out, aux, dropped


def _moe_tokens(p, xt, *, n_experts: int, top_k: int, capacity_factor: float,
                act: str, router_noise: float = 0.0, key=None,
                norm_topk_prob: bool = True, expert_offset: int = 0):
    """Core routed-expert computation over a flat token list (T, d):
    ``(out, aux, dropped)``, ``out`` the part of the layer's result this
    share's experts give (plus the shared experts)."""
    T, d = xt.shape
    held = p["experts"]["down"]["w"].shape[0]
    with jax.named_scope("moe"), jax.named_scope("dispatch"):
        logits = (xt.astype(jnp.float32) @ p["router"]["w"])  # (T, E)
        if router_noise and key is not None:
            logits = logits + router_noise * jax.random.normal(key, logits.shape)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, experts_idx = jax.lax.top_k(probs, top_k)  # (T, k)
        if norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)

        C = capacity_for(T, n_experts, top_k, capacity_factor)
        A = T * top_k  # total assignments
        flat_e = experts_idx.reshape(A)
        flat_w = gate_vals.reshape(A)
        order = jnp.argsort(flat_e, stable=True)  # assignments grouped by expert
        sorted_e = flat_e[order]
        token_of = order // top_k  # original token per sorted assignment
        # position within the expert's group (over ALL experts: the capacity
        # rule is the whole layer's, whichever share computes it)
        starts = jnp.searchsorted(sorted_e, jnp.arange(n_experts))  # (E,)
        pos_in_group = jnp.arange(A) - starts[sorted_e]
        local_e = sorted_e - expert_offset
        mine = (local_e >= 0) & (local_e < held)
        keep = mine & (pos_in_group < C)
        dropped = jnp.sum(mine & (pos_in_group >= C))
        slot = local_e * C + pos_in_group  # (A,) target slot in (held*C) buffer
        slot_safe = jnp.where(keep, slot, held * C)  # OOB -> dropped

    # dispatch: (E*C,) token indices. Empty slots point at token 0 (NOT a
    # concatenated pad row — appending a row reshards the token array and
    # costs a cross-shard all-reduce of the dispatched tensor, §Perf H2 it-3);
    # their combine weight is 0 so the garbage compute is ignored.
        disp_idx = jnp.zeros((held * C,), jnp.int32)
        disp_idx = disp_idx.at[slot_safe].set(token_of.astype(jnp.int32),
                                              mode="drop")
        xe = xt[disp_idx].reshape(held, C, d)

    with jax.named_scope("moe"), jax.named_scope("experts"):
        ye = _expert_mlp(p["experts"], xe, act)  # (held, C, d)

    # combine in SLOT space (§Perf H2 it-4): scatter-add straight from the
    # expert-sharded (E*C, d) outputs into token space. The assignment-space
    # gather ye_flat[slot] would materialize a (T*top_k, d) tensor that the
    # partitioner all-reduces across the expert axis; the slot-space scatter
    # keeps updates expert-sharded and reduces only the (T, d) output.
    # Weights stay in the activation dtype (f32 promotion doubles the
    # collective — §Perf H2 it-1).
    with jax.named_scope("moe"), jax.named_scope("combine"):
        w_kept = jnp.where(keep, flat_w[order], 0.0).astype(ye.dtype)
        w_slot = jnp.zeros((held * C,), ye.dtype)
        w_slot = w_slot.at[slot_safe].set(w_kept, mode="drop")
        contrib_slots = ye.reshape(held * C, d) * w_slot[:, None]
        out = jnp.zeros((T, d), ye.dtype).at[disp_idx].add(contrib_slots)

    if "shared" in p:
        with jax.named_scope("moe"), jax.named_scope("experts"):
            out = out + mlp(p["shared"], xt, act=act)

    # Switch-style load-balance loss: E * sum_e f_e * p_e
    assign_frac = jnp.zeros((n_experts,), jnp.float32).at[flat_e].add(1.0) / A
    prob_frac = probs.mean(axis=0)
    aux = n_experts * jnp.sum(assign_frac * prob_frac)
    return out.astype(xt.dtype), aux, dropped
