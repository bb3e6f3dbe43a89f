"""deepseek-v2-lite [moe] — 27L d_model=2048 16H MLA (kv_lora=512, no
q-LoRA, nope 128 / rope 64 / v 128) vocab=102400 untied; MoE: 2 shared + 64
routed experts, greedy softmax top-6 with the gates NOT renormalised
(``norm_topk_prob: false``, ``routed_scaling_factor`` 1), expert d_ff=1408,
first layer dense (d_ff=10944); rope_theta 10000, rms_norm_eps 1e-6.
[huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]

The published ``rope_scaling`` (YaRN, factor 40) belongs to the context
extension after the 4K pre-training stage and is not applied here.
"""
from repro.configs.base import ArchConfig, MLASpec, MoESpec, register

CONFIG = register(ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,        # MLA: per-head K/V (latent-compressed)
    head_dim=128,
    d_ff=10944,           # the single dense (non-MoE) first layer
    vocab=102400,
    mla=MLASpec(kv_lora=512, q_lora=0, qk_nope=128, qk_rope=64, v_head=128),
    moe=MoESpec(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                first_dense=1, norm_topk_prob=False),
    rope_theta=1e4,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite "
           "(config.json); arXiv:2405.04434",
))
