"""Coded data-parallel training of a DeepSeek-V2 decoder on one chip's
share: the configuration kind's data and work counts.

The configuration file holds the model's published ``config.json`` keys
(``n_routed_experts`` the experts held here, the published count under
``published``), the sequence length, the code and the bucket width.  Data,
from the run's seed and on the device: a pool of ``batch_pool`` batches of
``K`` shards × ``sequences_per_shard`` sequences of ``seq_len + 1`` token
ids uniform over the vocabulary slice, used in turn (the labels are the
ids shifted by one).  The drivers that run it are
``drivers/train.<driver>.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["work_counts", "make_batches", "capacity", "matmul_macs",
           "param_count"]

_F32 = 4


def capacity(cfg: dict) -> int:
    """Assignments an expert keeps per shard: ``T·k·f / E`` plus one,
    rounded up to a multiple of 8 (the program's rule), over the published
    expert count."""
    T = cfg["sequences_per_shard"] * cfg["seq_len"]
    E = cfg["published"]["n_routed_experts"]
    c = int(T * cfg["num_experts_per_tok"] * cfg["capacity_factor"] / E) + 1
    return max(8, c + (-c) % 8)


def _layer_macs(cfg: dict) -> tuple:
    """Per-token multiply-adds of one layer's weights: (MLA, dense FFN,
    MoE), the MoE at the held experts' average share of the top-k."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    L = cfg["kv_lora_rank"]
    mla = d * H * (dn + dr) + d * L + d * dr + L * H * (dn + dv) + H * dv * d
    ffn = 3 * d * cfg["intermediate_size"]
    E = cfg["published"]["n_routed_experts"]
    fe = cfg["moe_intermediate_size"]
    held_share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E
    moe = (d * E + 3 * d * fe * cfg["n_shared_experts"]
           + held_share * 3 * d * fe)
    return mla, ffn, moe


def matmul_macs(cfg: dict) -> float:
    """Multiply-adds of one token's forward pass: the weights' and causal
    attention's (a token attends to (S + 1) / 2 keys on average)."""
    mla, ffn, moe = _layer_macs(cfg)
    n = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    S = cfg["seq_len"]
    H = cfg["num_attention_heads"]
    attn = H * (S + 1) / 2 * (cfg["qk_nope_head_dim"]
                              + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return n * (mla + attn) + dense * ffn + (n - dense) * moe + head


def param_count(cfg: dict) -> int:
    """Parameters held here (as :meth:`repro.models.Model.init` lays them
    out: norms, MLA, FFN or router, held and shared experts, embedding
    and head)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    L = cfg["kv_lora_rank"]
    mla = d * H * (dn + dr) + d * L + L + d * dr + L * H * (dn + dv) \
        + H * dv * d
    fe = cfg["moe_intermediate_size"]
    moe = (d * cfg["published"]["n_routed_experts"]
           + 3 * d * fe * (cfg["n_shared_experts"] + cfg["n_routed_experts"]))
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    layers = n * (mla + 2 * d) + dense * 3 * d * cfg["intermediate_size"] \
        + (n - dense) * moe
    return layers + 2 * cfg["vocab_size"] * d + d


def work_counts(cfg: dict) -> dict:
    """Least bytes and operations of each layer of one step, from the
    shapes alone:

    * model: the forward and backward matmuls of every shard (3 × 2 ×
      the forward's multiply-adds per token × the step's tokens; recomputed
      work does not count), reading the parameters twice and writing the K
      shard gradients once, and AdamW (read parameters, both moments and
      the gradient, write the first three);
    * reduce: gradagg's encode and decode at the gradient's length: read
      the K shards, write the N symbols, the parity symbols combinations
      of the K shards (2·(N−K)·K·P operations); read the N symbols, write
      the K recovered shards;
    * uncoded: the plain training step: the same matmuls, one gradient
      written, and AdamW.
    """
    P = param_count(cfg)
    K = cfg["K"]
    N = K + cfg["parity_workers"]
    tokens = K * cfg["sequences_per_shard"] * cfg["seq_len"]
    flops = 6 * matmul_macs(cfg) * tokens
    adamw = 7 * P * _F32
    return {"model": {"bytes": (2 + K) * P * _F32 + adamw, "flops": flops},
            "reduce": {"bytes": 2 * (K + N) * P * _F32,
                       "flops": 2 * (N - K) * K * P},
            "uncoded": {"bytes": 3 * P * _F32 + adamw, "flops": flops}}


@partial(jax.jit, static_argnames=("shape", "vocab"))
def make_batches(key, shape: tuple, vocab: int):
    """``(tokens, labels)`` of ``shape + (seq_len,)``, from ``shape +
    (seq_len + 1,)`` ids uniform over ``[0, vocab)``."""
    ids = jax.random.randint(key, shape, 0, vocab, dtype=jnp.int32)
    return ids[..., :-1], ids[..., 1:]
