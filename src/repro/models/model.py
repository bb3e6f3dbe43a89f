"""Unified model assembly for the architecture zoo.

One generic decoder(-encoder) implementation is specialized entirely by
``ArchConfig``: mixer per layer (GQA / MLA / Mamba / RWKV-6), FFN per layer
(dense / MoE / RWKV channel-mix), optional encoder stack (whisper) and
modality stubs (VLM patch embeddings, audio frame embeddings).

Layer stacking uses ``stack_plan()``: an unrolled prefix (e.g. the single
dense layer of DeepSeek-V2/Kimi) plus a ``lax.scan`` over parameter-stacked
period blocks (period 8 for Jamba's 1-attention:7-mamba interleave) — this
keeps HLO size and compile time flat in depth, which matters for the 40x2
dry-run matrix.

Three entry points per model (the shapes of the assignment):
  ``loss_fn / forward``  — training forward (train_4k)
  ``prefill``            — full-sequence cache build (prefill_32k)
  ``decode_step``        — single-token with cache (decode_32k, long_500k)
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import ssm as S
from repro.models.layers import (
    cross_entropy_loss,
    dense,
    embed_init,
    layernorm,
    layernorm_init,
    mlp,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
)
from repro.serving import kvcache as KV

__all__ = ["Model"]


def _sinusoidal(S: int, d: int, offset=0) -> jax.Array:
    pos = (jnp.arange(S) + offset)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d))
    pe = jnp.zeros((S, d))
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


class Model:
    def __init__(self, cfg: ArchConfig, *, remat: bool = True,
                 moe_aux_coef: float = 0.01, attn_chunk: int = 512,
                 unroll: bool = False, moe_groups: int = 1,
                 mamba_chunk: int | None = None, attn_remat: bool = False):
        # unroll=True: python loops instead of lax.scan over layer blocks and
        # attention chunks, so compiled.cost_analysis() counts every
        # iteration's flops (XLA counts while-loop bodies once). Used by the
        # dry-run; training/serving keep scan for compact HLO.
        self.cfg = cfg
        self.remat = remat
        self.moe_aux_coef = moe_aux_coef
        self.attn_chunk = attn_chunk
        self.unroll = unroll
        self.moe_groups = moe_groups  # §Perf H2: data-aligned MoE routing groups
        self.mamba_chunk = mamba_chunk  # chunked parallel-in-time SSM prefill
        # recompute MLA score chunks in the backward pass (a layer's
        # gradient then holds one chunk's scores, not all of S x S)
        self.attn_remat = attn_remat
        self.prefix_len, self.period = cfg.stack_plan()
        self.n_blocks = (cfg.n_layers - self.prefix_len) // self.period
        self.specs = cfg.layer_specs()

    # ------------------------------------------------------------ norms ---
    def _norm_init(self, d=None):
        d = d or self.cfg.d_model
        return (rmsnorm_init if self.cfg.norm == "rmsnorm" else layernorm_init)(
            d, self.cfg.jdtype)

    def _norm(self, p, x):
        if self.cfg.norm == "rmsnorm":
            return rmsnorm(p, x, self.cfg.norm_eps)
        return layernorm(p, x, self.cfg.norm_eps)

    # ------------------------------------------------------------- init ---
    def _init_layer(self, key, spec, *, decoder: bool) -> dict:
        cfg = self.cfg
        dt = cfg.jdtype
        mixer, ffn = spec
        ks = jax.random.split(key, 5)
        p: dict = {"ln1": self._norm_init()}
        if mixer == "attn":
            p["mixer"] = A.init_gqa(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.hd, qk_norm=cfg.qk_norm, bias=cfg.qkv_bias,
                                    dtype=dt)
            if cfg.enc_layers and decoder:
                p["ln_x"] = self._norm_init()
                p["cross"] = A.init_gqa(ks[1], cfg.d_model, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.hd, dtype=dt)
        elif mixer == "mla":
            m = cfg.mla
            p["mixer"] = A.init_mla(ks[0], cfg.d_model, cfg.n_heads,
                                    kv_lora=m.kv_lora, q_lora=m.q_lora,
                                    qk_nope=m.qk_nope, qk_rope=m.qk_rope,
                                    v_head=m.v_head, dtype=dt)
        elif mixer == "mamba":
            p["mixer"] = S.init_mamba(ks[0], cfg.d_model, d_state=cfg.mamba.d_state,
                                      d_conv=cfg.mamba.d_conv,
                                      expand=cfg.mamba.expand, dtype=dt)
        elif mixer == "rwkv":
            p["mixer"] = S.init_rwkv_time(ks[0], cfg.d_model,
                                          head_dim=cfg.rwkv.head_dim,
                                          decay_lora=cfg.rwkv.decay_lora, dtype=dt)
        p["ln2"] = self._norm_init()
        if ffn == "dense":
            p["ffn"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, act=cfg.act, dtype=dt)
        elif ffn == "moe":
            p["ffn"] = MOE.init_moe(ks[2], cfg.d_model, cfg.moe.d_ff_expert,
                                    cfg.moe.n_experts, n_shared=cfg.moe.n_shared,
                                    act=cfg.act if cfg.act != "relu2" else "swiglu",
                                    dtype=dt, held=cfg.moe.held,
                                    expert_offset=cfg.moe.held_offset)
        elif ffn == "rwkv_cm":
            p["ffn"] = S.init_rwkv_channel(ks[2], cfg.d_model, cfg.d_ff, dtype=dt)
        return p

    def init(self, key) -> dict:
        cfg = self.cfg
        dt = cfg.jdtype
        keys = jax.random.split(key, cfg.n_layers + 8)
        params: dict = {
            "embed": embed_init(keys[0], cfg.vocab, cfg.d_model, dt),
            "final_norm": self._norm_init(),
            "unembed": {"w": (jax.random.normal(keys[1], (cfg.vocab, cfg.d_model))
                              * 0.02).astype(dt)},
        }
        # unrolled prefix layers
        params["prefix"] = {
            str(i): self._init_layer(keys[2 + i], self.specs[i], decoder=True)
            for i in range(self.prefix_len)
        }
        # scanned body: one stacked subtree per position-in-period
        body: dict = {}
        for j in range(self.period):
            spec = self.specs[self.prefix_len + j]
            bkeys = jax.random.split(
                jax.random.fold_in(keys[2 + cfg.n_layers], j), self.n_blocks)
            body[f"sub{j}"] = jax.vmap(
                lambda k: self._init_layer(k, spec, decoder=True))(bkeys)
        params["blocks"] = body
        if cfg.enc_layers:
            ekeys = jax.random.split(keys[3 + cfg.n_layers], cfg.enc_layers)
            params["encoder"] = {
                "layers": jax.vmap(
                    lambda k: self._init_layer(k, ("attn", "dense"), decoder=False)
                )(ekeys),
                "final_norm": self._norm_init(),
            }
        return params

    # ------------------------------------------------------- layer apply ---
    def _apply_layer(self, p, spec, x, *, positions, mode, cache=None, pos=None,
                     enc_out=None, rng=None, stats=None):
        """Returns (x, new_cache, aux_loss).  A ``stats`` dict receives the
        layer's MoE ``dropped`` count (assignments over capacity)."""
        cfg = self.cfg
        mixer, ffn = spec
        aux = jnp.zeros((), jnp.float32)
        h = self._norm(p["ln1"], x)
        new_cache = cache
        use_rope = cfg.pos == "rope"

        if mixer == "attn":
            self_cache = cache["self"] if (cache is not None and cfg.enc_layers) else cache
            if mode in ("train", "prefill", "encode"):
                y, self_cache = A.gqa_forward(
                    p["mixer"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                    head_dim=cfg.hd, positions=positions, rope_theta=cfg.rope_theta,
                    causal=(mode != "encode"), chunk=self.attn_chunk,
                    cache=self_cache if mode == "prefill" else None,
                    use_rope=use_rope and mode != "encode", unroll=self.unroll)
            else:
                y, self_cache = A.gqa_decode(
                    p["mixer"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                    head_dim=cfg.hd, pos=pos, cache=self_cache,
                    rope_theta=cfg.rope_theta, use_rope=use_rope)
            x = x + y
            cross_cache = cache["cross"] if (cache is not None and cfg.enc_layers) else None
            if "cross" in p and (enc_out is not None or cross_cache is not None):
                h2 = self._norm(p["ln_x"], x)
                if mode in ("train", "prefill"):
                    y2, cross_cache = A.gqa_forward(
                        p["cross"], h2, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, positions=positions, chunk=self.attn_chunk,
                        kv_source=enc_out, unroll=self.unroll,
                        cache=cross_cache if mode == "prefill" else None)
                else:
                    y2, cross_cache = A.gqa_decode(
                        p["cross"], h2, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        head_dim=cfg.hd, pos=pos, cache=cross_cache, cross=True)
                x = x + y2
            if cache is not None and cfg.enc_layers:
                new_cache = {"self": self_cache, "cross": cross_cache}
            else:
                new_cache = self_cache
        elif mixer == "mla":
            m = cfg.mla
            kw = dict(n_heads=cfg.n_heads, kv_lora=m.kv_lora, qk_nope=m.qk_nope,
                      qk_rope=m.qk_rope, v_head=m.v_head, rope_theta=cfg.rope_theta)
            if mode in ("train", "prefill"):
                with jax.named_scope("mla"):
                    y, new_cache = A.mla_forward(
                        p["mixer"], h, positions=positions,
                        chunk=self.attn_chunk, unroll=self.unroll,
                        remat=self.attn_remat,
                        cache=cache if mode == "prefill" else None, **kw)
            else:
                y, new_cache = A.mla_decode(p["mixer"], h, pos=pos, cache=cache, **kw)
            x = x + y
        elif mixer == "mamba":
            if mode in ("train", "prefill"):
                if mode == "prefill":
                    y, new_cache = S.mamba_forward(p["mixer"], h,
                                                   d_state=cfg.mamba.d_state,
                                                   return_state=True,
                                                   chunk=self.mamba_chunk)
                else:
                    y = S.mamba_forward(p["mixer"], h, d_state=cfg.mamba.d_state,
                                        chunk=self.mamba_chunk)
            else:
                y, new_cache = S.mamba_decode(p["mixer"], h, cache,
                                              d_state=cfg.mamba.d_state)
            x = x + y
        elif mixer == "rwkv":
            if mode in ("train", "prefill"):
                if mode == "prefill":
                    y, st = S.rwkv_time_forward(p["mixer"], h,
                                                head_dim=cfg.rwkv.head_dim,
                                                return_state=True)
                    new_cache = dict(cache) if cache else {}
                    new_cache.update(st)
                else:
                    y = S.rwkv_time_forward(p["mixer"], h, head_dim=cfg.rwkv.head_dim)
            else:
                y, st = S.rwkv_time_decode(p["mixer"], h,
                                           {"S": cache["S"], "last_x": cache["last_x"]},
                                           head_dim=cfg.rwkv.head_dim)
                new_cache = dict(cache)
                new_cache.update(st)
            x = x + y
        else:
            raise ValueError(mixer)

        h = self._norm(p["ln2"], x)
        if ffn == "dense":
            x = x + mlp(p["ffn"], h, act=cfg.act)
        elif ffn == "moe":
            y, aux, dropped = MOE.moe_forward(
                p["ffn"], h, n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor,
                act=cfg.act if cfg.act != "relu2" else "swiglu",
                key=rng, groups=self.moe_groups,
                norm_topk_prob=cfg.moe.norm_topk_prob,
                expert_offset=cfg.moe.held_offset)
            if stats is not None:
                stats["dropped"] = dropped
            x = x + y
        elif ffn == "rwkv_cm":
            if mode in ("train", "prefill", "encode"):
                x = x + S.rwkv_channel_forward(p["ffn"], h)
                if mode == "prefill":
                    new_cache = dict(new_cache)
                    new_cache["cm_last_x"] = h[:, -1]
            else:
                y, st = S.rwkv_channel_decode(p["ffn"], h, {"last_x": cache["cm_last_x"]})
                x = x + y
                new_cache = dict(new_cache)
                new_cache["cm_last_x"] = st["last_x"]
        return x, new_cache, aux

    # ---------------------------------------------------------- encoder ---
    def _encode(self, params, frames):
        cfg = self.cfg
        x = frames + _sinusoidal(frames.shape[1], cfg.d_model).astype(frames.dtype)
        positions = jnp.arange(frames.shape[1])

        def body(carry, lp):
            h, _ = carry
            h, _, _ = self._apply_layer(lp, ("attn", "dense"), h,
                                        positions=positions, mode="encode")
            return (h, 0.0), None

        (x, _), _ = jax.lax.scan(body, (x, 0.0), params["encoder"]["layers"])
        return self._norm(params["encoder"]["final_norm"], x)

    # ---------------------------------------------------------- forward ---
    def _embed_inputs(self, params, batch, *, offset: int = 0):
        """Returns (x, positions, enc_out)."""
        cfg = self.cfg
        toks = batch["tokens"]
        x = params["embed"]["table"][toks]
        enc_out = None
        if cfg.family == "vlm" and "patches" in batch:
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
        if cfg.family == "audio":
            enc_out = self._encode(params, batch["frames"])
        S_tot = x.shape[1]
        positions = jnp.arange(S_tot) + offset
        if cfg.pos == "sinusoidal":
            x = x + _sinusoidal(S_tot, cfg.d_model, offset).astype(x.dtype)
        return x, positions, enc_out

    def forward(self, params, batch, *, rng=None):
        """Full-sequence training forward. Returns (logits, aux_loss)."""
        x, positions, enc_out = self._embed_inputs(params, batch)
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(self.prefix_len):
            x, _, aux = self._apply_layer(params["prefix"][str(i)], self.specs[i], x,
                                          positions=positions, mode="train",
                                          enc_out=enc_out, rng=rng)
            aux_total = aux_total + aux

        body_specs = [self.specs[self.prefix_len + j] for j in range(self.period)]

        def block_fn(carry, bp):
            h, aux_c = carry
            for j in range(self.period):
                h, _, a = self._apply_layer(bp[f"sub{j}"], body_specs[j], h,
                                            positions=positions, mode="train",
                                            enc_out=enc_out, rng=rng)
                aux_c = aux_c + a
            return (h, aux_c), None

        fn = jax.checkpoint(block_fn) if self.remat else block_fn
        if self.unroll:
            for b in range(self.n_blocks):
                bp = jax.tree.map(lambda a: a[b], params["blocks"])
                (x, aux_total), _ = fn((x, aux_total), bp)
        else:
            (x, aux_total), _ = jax.lax.scan(fn, (x, aux_total), params["blocks"])
        x = self._norm(params["final_norm"], x)
        logits = (x @ params["unembed"]["w"].T).astype(jnp.float32)
        return logits, aux_total / max(self.cfg.n_layers, 1)

    def loss_fn(self, params, batch, *, rng=None):
        logits, aux = self.forward(params, batch, rng=rng)
        cfg = self.cfg
        if cfg.family == "vlm" and "patches" in batch:
            P = batch["patches"].shape[1]
            S_text = batch["tokens"].shape[1]
            logits = jax.lax.dynamic_slice_in_dim(logits, P - 1, S_text, axis=1)
            labels = batch["tokens"]
        else:
            labels = batch["labels"]
        ce = cross_entropy_loss(logits, labels, batch.get("loss_mask"))
        return ce + self.moe_aux_coef * aux

    # ---------------------------------------------------------- serving ---
    def init_cache(self, B: int, max_len: int, *, window: int | None = None):
        cfg = self.cfg
        dt = cfg.jdtype
        W = min(window or max_len, max_len)

        def one(mixer):
            return KV.make_layer_cache(cfg, mixer, B, W, dt)

        cache = {"prefix": {str(i): one(self.specs[i][0])
                            for i in range(self.prefix_len)}}
        body = {}
        for j in range(self.period):
            c = one(self.specs[self.prefix_len + j][0])
            if self.specs[self.prefix_len + j][1] == "rwkv_cm":
                c["cm_last_x"] = jnp.zeros((B, cfg.d_model), dt)
            body[f"sub{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (self.n_blocks,) + a.shape), c)
        cache["blocks"] = body
        return cache

    def prefill(self, params, batch, cache, *, rng=None):
        """Run the full prompt, writing caches. Returns (last_logits, cache)."""
        x, positions, enc_out = self._embed_inputs(params, batch)
        new_cache = {"prefix": {}}
        for i in range(self.prefix_len):
            x, c, _ = self._apply_layer(params["prefix"][str(i)], self.specs[i], x,
                                        positions=positions, mode="prefill",
                                        cache=cache["prefix"][str(i)],
                                        enc_out=enc_out, rng=rng)
            new_cache["prefix"][str(i)] = c

        body_specs = [self.specs[self.prefix_len + j] for j in range(self.period)]

        def block_fn(h, xs):
            bp, bc = xs
            ncs = {}
            for j in range(self.period):
                h, nc, _ = self._apply_layer(bp[f"sub{j}"], body_specs[j], h,
                                             positions=positions, mode="prefill",
                                             cache=bc[f"sub{j}"], enc_out=enc_out,
                                             rng=rng)
                ncs[f"sub{j}"] = nc
            return h, ncs

        if self.unroll:
            percs = []
            for b in range(self.n_blocks):
                bp = jax.tree.map(lambda a: a[b], params["blocks"])
                bc = jax.tree.map(lambda a: a[b], cache["blocks"])
                x, nc = block_fn(x, (bp, bc))
                percs.append(nc)
            body_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *percs)
        else:
            x, body_caches = jax.lax.scan(block_fn, x,
                                          (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = body_caches
        x = self._norm(params["final_norm"], x)
        logits = (x[:, -1:] @ params["unembed"]["w"].T).astype(jnp.float32)
        return logits, new_cache

    def decode_step(self, params, token, pos, cache):
        """token: (B, 1) int32; pos: scalar int32. Returns (logits, cache)."""
        cfg = self.cfg
        x = params["embed"]["table"][token]
        if cfg.pos == "sinusoidal":
            x = x + _sinusoidal(1, cfg.d_model, pos).astype(x.dtype)
        positions = jnp.arange(1) + pos
        new_cache = {"prefix": {}}
        for i in range(self.prefix_len):
            x, c, _ = self._apply_layer(params["prefix"][str(i)], self.specs[i], x,
                                        positions=positions, mode="decode",
                                        cache=cache["prefix"][str(i)], pos=pos)
            new_cache["prefix"][str(i)] = c

        body_specs = [self.specs[self.prefix_len + j] for j in range(self.period)]

        def block_fn(h, xs):
            bp, bc = xs
            ncs = {}
            for j in range(self.period):
                h, nc, _ = self._apply_layer(bp[f"sub{j}"], body_specs[j], h,
                                             positions=positions, mode="decode",
                                             cache=bc[f"sub{j}"], pos=pos)
                ncs[f"sub{j}"] = nc
            return h, ncs

        if self.unroll:
            percs = []
            for b in range(self.n_blocks):
                bp = jax.tree.map(lambda a: a[b], params["blocks"])
                bc = jax.tree.map(lambda a: a[b], cache["blocks"])
                x, nc = block_fn(x, (bp, bc))
                percs.append(nc)
            body_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *percs)
        else:
            x, body_caches = jax.lax.scan(block_fn, x,
                                          (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = body_caches
        x = self._norm(params["final_norm"], x)
        logits = (x @ params["unembed"]["w"].T).astype(jnp.float32)
        return logits, new_cache

    # ------------------------------------------------------------ sizes ---
    def param_count(self, params) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))

    def active_param_count(self, params) -> int:
        """Parameters touched per token (MoE: top_k of n_experts routed)."""
        cfg = self.cfg
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
            sz = int(leaf.size)
            if "experts" in keys and cfg.moe:
                sz = sz * cfg.moe.top_k // cfg.moe.n_experts
            total += sz
        return total
