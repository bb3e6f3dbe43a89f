"""The coded data-parallel training step, reduced one bucket at a time.

The batch is split into K shards; shard ``i``'s gradient ``g_i / K`` is the
i-th systematic symbol of the aggregator's code (:mod:`repro.core.grad_agg`).
The step never holds K copies of the whole model's gradient: as data-parallel
training reduces its buckets during the backward pass, it walks the model's
layers from the loss down, and for each layer

1. ``train_grad_program`` computes the K shards' gradients of that layer
   alone (the layer's forward recomputed from its stored input, one shard at
   a time), cut into fixed-width buckets (:class:`BucketLayout`), and the
   cotangent that goes on to the layer below;
2. ``reduce_program`` runs each bucket through the coded sum
   (:meth:`CodedAggregator.aggregate`: parity symbols, the straggler erase,
   the peeling decode, zero-fill and sum), under the worker mask the caller
   gives.

``train_fwd_program`` runs first (every shard's forward, keeping each
layer's input, the loss and the MoE drop count) and ``train_opt_program``
last (AdamW on the summed gradient, parameters and state donated).  The
programs are separate jitted functions so that device time per program can
be read from a profile; the host dispatches them all without waiting and
fetches the loss, the unresolved count and the drop count once, at the end.

Stages: the head (final norm, output projection and loss), each layer, and
the embedding.  A layer of the scanned body is read from its block's
stacked parameters with the block index as an operand, so layers of one
shape share one compiled program.  Text decoders only: no encoder, no patch
embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.grad_agg import DDP_BUCKET_ELEMS, BucketLayout, CodedAggregator
from repro.models import Model
from repro.models.layers import cross_entropy_loss
from repro.obs import metrics as _obs_metrics
from repro.obs.trace import span as _span
from repro.optim import AdamWConfig, adamw_update

__all__ = ["CodedTrainStep", "StepResult"]


class StepResult(NamedTuple):
    params: dict
    opt_state: object
    loss: float             # mean of the shards' losses
    unresolved: int         # shards the decode left unresolved (per bucket)
    dropped: int            # routed assignments over capacity, all layers
    sums: dict              # stage -> its buckets' coded sums, (cap,) each


@dataclasses.dataclass(frozen=True)
class _Stage:
    name: str               # "head" | "layers.<l>" | "embed"
    kind: str               # "head" | "layer" | "embed"
    spec: tuple             # a layer's (mixer, ffn)
    where: tuple            # ("prefix", i) or ("blocks", j, b): its params
    layout: BucketLayout


class CodedTrainStep:
    """``step(params, opt_state, batch, worker_mask) -> StepResult``."""

    def __init__(self, model: Model, agg: CodedAggregator,
                 opt: AdamWConfig = AdamWConfig(), *,
                 bucket_cap: int = DDP_BUCKET_ELEMS):
        cfg = model.cfg
        if cfg.enc_layers or cfg.family in ("vlm", "audio"):
            raise NotImplementedError(
                "the per-bucket coded step covers text decoders only")
        self.model, self.agg, self.opt = model, agg, opt
        self.K = agg.n_shards
        self.bucket_cap = bucket_cap
        self._stages: list | None = None
        self._fwd = jax.jit(self._forward_fn())
        grad_fns = self._grad_fns()
        self._grad = {k: jax.jit(f, static_argnums=0)
                      for k, f in grad_fns.items()}
        self._reduce = jax.jit(self._reduce_fn())
        self._opt = jax.jit(self._opt_fn(), donate_argnums=(0, 1))

    # ------------------------------------------------------------ layout

    def _layers(self) -> list:
        """``(spec, where)`` of every layer, in order."""
        m = self.model
        out = [(m.specs[i], ("prefix", i)) for i in range(m.prefix_len)]
        out += [(m.specs[m.prefix_len + j], ("blocks", j, b))
                for b in range(m.n_blocks) for j in range(m.period)]
        return out

    @staticmethod
    def _stacked(params, where):
        """A layer's parameters as stored: a prefix layer's own tree, or
        its block position's tree stacked over blocks."""
        if where[0] == "prefix":
            return params["prefix"][str(where[1])]
        return params["blocks"][f"sub{where[1]}"]

    def stages(self, params) -> list:
        """The stages from the loss down, each with its bucket layout."""
        if self._stages is None:
            cap = self.bucket_cap
            st = [_Stage("head", "head", (), (),
                         BucketLayout.of(_head_params(params), cap))]
            for l, (spec, where) in reversed(list(enumerate(self._layers()))):
                p = self._stacked(params, where)
                if where[0] == "blocks":
                    p = jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                        p)
                st.append(_Stage(f"layers.{l}", "layer", spec, where,
                                 BucketLayout.of(p, cap)))
            st.append(_Stage("embed", "embed", (), (),
                             BucketLayout.of(params["embed"], cap)))
            self._stages = st
        return self._stages

    def grads_from_sums(self, params, sums: dict):
        """The gradient tree the step applied, from its bucket sums."""
        grads: dict = {"prefix": {}, "blocks": {}}
        body: dict = {}
        for s in self.stages(params):
            g = s.layout.unpack(sums[s.name])
            if s.kind == "head":
                grads.update(g)
            elif s.kind == "embed":
                grads["embed"] = g
            elif s.where[0] == "prefix":
                grads["prefix"][str(s.where[1])] = g
            else:
                body.setdefault(s.where[1], {})[s.where[2]] = g
        for j, per_block in body.items():
            grads["blocks"][f"sub{j}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[per_block[b] for b in sorted(per_block)])
        return grads

    # ---------------------------------------------------------- programs

    def _layer(self, p, spec, x, positions, stats=None):
        x, _, aux = self.model._apply_layer(p, spec, x, positions=positions,
                                            mode="train", stats=stats)
        return x, aux

    def _head_loss(self, hp, x, labels):
        m = self.model
        x = m._norm(hp["final_norm"], x)
        logits = (x @ hp["unembed"]["w"].T).astype(jnp.float32)
        return cross_entropy_loss(logits, labels)

    def _forward_fn(self):
        m = self.model
        layers = self._layers()

        def train_fwd_program(params, tokens, labels):
            def one(args):
                toks, labs = args
                x, positions, _ = m._embed_inputs(params, {"tokens": toks})
                xs = [x]
                aux = jnp.zeros((), jnp.float32)
                dropped = jnp.zeros((), jnp.int32)
                for spec, where in layers:
                    p = self._stacked(params, where)
                    if where[0] == "blocks":
                        p = jax.tree.map(lambda a: a[where[2]], p)
                    st: dict = {}
                    x, a = self._layer(p, spec, x, positions, st)
                    aux, dropped = aux + a, dropped + st.get("dropped", 0)
                    xs.append(x)
                ce = self._head_loss(_head_params(params), x, labs)
                loss = ce + m.moe_aux_coef * aux / max(len(layers), 1)
                return tuple(xs), loss, dropped

            xs, loss, dropped = jax.lax.map(one, (tokens, labels))
            return xs, loss.mean(), dropped.sum()

        return train_fwd_program

    def _grad_fns(self) -> dict:
        """The three ``train_grad_program`` variants, each ``fn(static,
        ...) -> (K-deep buckets, cotangent of the stage's input)``: one
        shard at a time, the gradient already divided by K."""
        m, K = self.model, self.K
        aux_ct = jnp.float32(m.moe_aux_coef / max(m.cfg.n_layers, 1) / K)

        def layer(static, p, b, x_in, ct_out):
            layout, spec = static
            if b is not None:
                p = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, b, 0, False), p)
            positions = jnp.arange(x_in.shape[2])

            def one(args):
                x, ct = args
                _, vjp = jax.vjp(
                    lambda p_, x_: self._layer(p_, spec, x_, positions), p, x)
                gp, gx = vjp((ct, aux_ct))
                return layout.pack(gp), gx

            return jax.lax.map(one, (x_in, ct_out))

        def head(layout, hp, x_in, labels):
            def one(args):
                x, labs = args
                _, vjp = jax.vjp(lambda hp_, x_: self._head_loss(hp_, x_, labs),
                                 hp, x)
                gp, gx = vjp(jnp.float32(1.0 / K))
                return layout.pack(gp), gx

            return jax.lax.map(one, (x_in, labels))

        def embed(layout, ep, tokens, ct_out):
            def one(args):
                toks, ct = args
                _, vjp = jax.vjp(lambda ep_: m._embed_inputs(
                    {"embed": ep_}, {"tokens": toks})[0], ep)
                (gp,) = vjp(ct)
                return layout.pack(gp)

            return jax.lax.map(one, (tokens, ct_out))

        return {k: _named(f, "train_grad_program") for k, f in
                {"layer": layer, "head": head, "embed": embed}.items()}

    def _reduce_fn(self):
        agg = self.agg

        def reduce_program(bucket, worker_mask):
            with jax.named_scope("reduce"):
                return agg.aggregate(bucket, worker_mask)

        return reduce_program

    def _opt_fn(self):
        def train_opt_program(params, opt_state, sums):
            grads = self.grads_from_sums(params, sums)
            return adamw_update(params, grads, opt_state, self.opt)

        return train_opt_program

    # ------------------------------------------------------------- step

    def __call__(self, params, opt_state, batch, worker_mask) -> StepResult:
        K = self.K
        with _span("train/inputs", lane="train"):
            tokens = jnp.asarray(batch["tokens"])
            labels = jnp.asarray(batch["labels"])
            if tokens.shape[0] % K:
                raise ValueError(f"batch size {tokens.shape[0]} not "
                                 f"divisible by n_shards={K}")
            # shard i: sequences [i·B/K, (i+1)·B/K)
            tokens = tokens.reshape(K, -1, tokens.shape[1])
            labels = labels.reshape(K, -1, labels.shape[1])
            mask = jnp.asarray(worker_mask, bool)
            if mask.shape != (self.agg.n_workers,):
                raise ValueError(f"worker_mask must be "
                                 f"({self.agg.n_workers},); got {mask.shape}")
        reg = _obs_metrics.active()
        stages = self.stages(params)
        with _span("train/grads", lane="train", stage="forward"):
            xs, loss, dropped = self._fwd(params, tokens, labels)
        xs = list(xs)
        sums: dict = {}
        n_unres = None
        ct = None
        for s in stages:
            with _span("train/grads", lane="train", stage=s.name):
                if s.kind == "head":
                    buckets, ct = self._grad["head"](
                        s.layout, _head_params(params), xs.pop(), labels)
                elif s.kind == "embed":
                    buckets = self._grad["embed"](s.layout, params["embed"],
                                                  tokens, ct)
                else:
                    b = (jnp.int32(s.where[2]) if s.where[0] == "blocks"
                         else None)
                    buckets, ct = self._grad["layer"](
                        (s.layout, s.spec), self._stacked(params, s.where), b,
                        xs.pop(), ct)
            with _span("train/reduce", lane="train", stage=s.name):
                out = [self._reduce(bk, mask) for bk in buckets]
                del buckets
                sums[s.name] = tuple(t for t, _ in out)
                n_unres = out[0][1]
            if reg is not None:
                reg.counter("grad_agg.buckets_total",
                            layer=s.name).inc(len(out))
        params, opt_state = self._opt(params, opt_state, sums)
        with _span("train/fetch", lane="train"):
            loss, n_unres, n_drop = jax.device_get((loss, n_unres, dropped))
        if reg is not None:
            reg.counter("grad_agg.unresolved_total").inc(int(n_unres))
            reg.counter("moe.tokens_dropped_total").inc(int(n_drop))
        return StepResult(params, opt_state, float(loss), int(n_unres),
                          int(n_drop), sums)


def _head_params(params) -> dict:
    return {"final_norm": params["final_norm"], "unembed": params["unembed"]}


def _named(fn, name: str):
    fn.__name__ = fn.__qualname__ = name
    return fn
