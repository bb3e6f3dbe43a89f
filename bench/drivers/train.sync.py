"""The sync driver of a training configuration: one coded data-parallel
training step per call, :class:`repro.train.coded_step.CodedTrainStep` (the
``Trainer``'s coded step, the one ``launch/train.py --coded-agg`` drives),
under the call's worker mask, from the parameters and AdamW state the
previous call left.

Each checked call is compared with the plain reference
(:mod:`bench.kinds.dsv2_reference`) at the call's own input parameters: the
reference computes the K shard gradients one at a time, sums those its own
peeling resolves over K, and the applied gradient (the coded bucket sums,
laid out as the parameters) and the loss are compared with it.  The warm
calls' updates are compared too: the reference's AdamW, its state carried
from zero, applied to each warm call's input parameters and applied
gradient, against the next call's input parameters.

The checked calls' input parameters and applied gradients are copied to
the host (about 2.1 GB each at the configuration's size), so the host
holds a few: the warm calls and ``min(traffic["checked_calls"],
cfg["checked_window_calls"])`` window calls drawn from the seed among the
first ``cfg["checked_window_from"]`` of the window.  Data and work counts
are the kind's (:mod:`bench.kinds.train`).
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.kinds import dsv2_reference as ref
from bench.kinds import seed_key, train

__all__ = ["Cell"]


def model_config(cfg: dict):
    """The program's :class:`ArchConfig` of a configuration file: the
    registered architecture at the file's depth, vocabulary slice and
    expert share, in float32."""
    from repro.configs import get_config

    arch = get_config("deepseek-v2-lite")
    moe = dataclasses.replace(
        arch.moe, n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], d_ff_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"], capacity_factor=cfg["capacity_factor"],
        first_dense=cfg["first_k_dense_replace"],
        norm_topk_prob=cfg["norm_topk_prob"], held=cfg["n_routed_experts"],
        held_offset=cfg["expert_offset"])
    mla = dataclasses.replace(
        arch.mla, kv_lora=cfg["kv_lora_rank"], q_lora=cfg["q_lora_rank"] or 0,
        qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
        v_head=cfg["v_head_dim"])
    return dataclasses.replace(
        arch, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], mla=mla, moe=moe, dtype="float32")


def reference_config(cfg: dict) -> dict:
    """The reference's settings of a configuration file."""
    return {"n_heads": cfg["num_attention_heads"],
            "qk_nope": cfg["qk_nope_head_dim"],
            "qk_rope": cfg["qk_rope_head_dim"], "v_head": cfg["v_head_dim"],
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": cfg["rms_norm_eps"],
            "n_experts": cfg["published"]["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "capacity": train.capacity(cfg),
            "expert_offset": cfg["expert_offset"],
            "norm_topk_prob": cfg["norm_topk_prob"],
            "aux_coef": aux_coef(cfg)}


def aux_coef(cfg: dict) -> float:
    """The program's auxiliary-loss weight: it weights the layers' summed
    balance losses by ``coef / n_layers``, and DeepSeek-V2 each MoE layer's
    by ``aux_loss_alpha``."""
    return cfg["aux_loss_alpha"] * cfg["num_hidden_layers"]


class _Out(NamedTuple):
    params: dict
    opt_state: tuple
    loss: float
    unresolved: int
    dropped: int
    sums: dict


class _ReferenceStep:
    """The reference in the program's place (the control): the coded
    gradient of :func:`ref.coded_grad` at ``precision``, plain AdamW."""

    def __init__(self, cell, precision: str):
        self.cell, self.precision = cell, precision

    def __call__(self, params, opt_state, batch, worker_mask) -> _Out:
        c = self.cell
        resolved, n_unres = c.reference_resolution(worker_mask)
        loss, g = ref.coded_grad(params, batch["tokens"], batch["labels"],
                                 resolved, c.rcfg, self.precision)
        m, v, t = opt_state
        params, m, v = ref.adamw(params, g, m, v, t + 1, lr=c.lr)
        return _Out(params, (m, v, t + 1), float(loss), n_unres, 0,
                    {"tree": g})

    @staticmethod
    def applied(_, sums):
        return sums["tree"]


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, log,
                 control: str | None = None) -> None:
        from repro.core.grad_agg import CodedAggregator
        from repro.models import Model

        if cfg["sequences_per_shard"] != 1:
            raise ValueError("the reference reads one sequence per shard")
        self.K, self.rounds = cfg["K"], cfg["decode_rounds"]
        self.rcfg = reference_config(cfg)
        self.lr = cfg["lr"]
        arch = model_config(cfg)

        t0 = time.perf_counter()
        kp, kb = jax.random.split(seed_key(seed))
        model = Model(arch, remat=False, attn_chunk=cfg["attn_chunk"],
                      attn_remat=True, moe_aux_coef=aux_coef(cfg))
        self._init = jax.jit(model.init)
        self._kp = kp
        self._shapes = jax.eval_shape(model.init, kp)
        self.params = self._init(kp)
        self.tokens, self.labels = train.make_batches(
            kb, (cfg["batch_pool"], self.K, cfg["seq_len"] + 1),
            cfg["vocab_size"])
        jax.block_until_ready((self.params, self.tokens))
        log("data_s", time.perf_counter() - t0)

        t0 = time.perf_counter()
        self.agg = CodedAggregator.build(
            self.K, redundancy=cfg["parity_workers"] / self.K,
            row_weight=cfg["row_weight"], seed=cfg["code_seed"],
            decode_iters=self.rounds, decode_backend=cfg["decode_backend"])
        code = self.agg.code
        self.n_workers = code.N
        self.h_cols = np.stack([np.flatnonzero(row) for row in code.H != 0])
        log("code_s", time.perf_counter() - t0)

        t0 = time.perf_counter()
        if control is None:
            from repro.optim import AdamWConfig, adamw_init
            from repro.train.coded_step import CodedTrainStep

            self.driver = CodedTrainStep(model, self.agg,
                                         AdamWConfig(lr=self.lr),
                                         bucket_cap=cfg["bucket_elems"])
            self.opt_state = adamw_init(self.params)
            self._applied = self.driver.grads_from_sums
        else:
            self.driver = _ReferenceStep(self, control)
            zeros = jax.tree.map(jnp.zeros_like, self.params)
            self.opt_state = (zeros, jax.tree.map(jnp.zeros_like, zeros),
                              jnp.int32(0))
            self._applied = _ReferenceStep.applied
        jax.block_until_ready(self.opt_state)
        log("build_s", time.perf_counter() - t0)

        self.warm = traffic["warm_calls"]
        rng = np.random.default_rng([seed, 0x7472])
        self._keep = set(range(self.warm)) | set((self.warm + rng.choice(
            cfg["checked_window_from"], min(traffic["checked_calls"],
                                            cfg["checked_window_calls"]),
            replace=False)).tolist())
        self.masks: list = []        # per call
        self.unresolved: list = []   # per call
        self.kept: dict = {}         # checked call -> (θ in, sums, loss)
        self.after_warm = None       # θ out of the last warm call

    def batch(self, i: int) -> dict:
        j = i % self.tokens.shape[0]
        return {"tokens": self.tokens[j], "labels": self.labels[j]}

    def call(self, i: int, worker_mask: np.ndarray) -> None:
        keep = i in self._keep
        # the first call's parameters are made again from the seed
        theta_in = None
        if keep and i:
            theta_in = (self.after_warm if i == self.warm
                        else jax.device_get(self.params))
        out = self.driver(self.params, self.opt_state, self.batch(i),
                          worker_mask)
        self.params, self.opt_state = out.params, out.opt_state
        jax.block_until_ready((self.params, self.opt_state))
        self.masks.append(np.asarray(worker_mask, bool))
        self.unresolved.append(int(out.unresolved))
        if keep:
            self.kept[i] = (theta_in, jax.device_get(out.sums), out.loss)
        if i == self.warm - 1:      # before the window: not timed
            self.after_warm = jax.device_get(self.params)

    def release(self) -> None:
        """Drop the system under test, its parameters, optimizer state and
        compiled programs; the data and the kept answers stay for the
        comparison (whose reference needs the chip's memory back)."""
        self.driver = self.params = self.opt_state = None
        jax.clear_caches()

    def reference_resolution(self, worker_mask) -> tuple:
        left = ref.peel_erased(self.h_cols, np.asarray(worker_mask, bool),
                               self.rounds)[:self.K]
        return ~left, int(left.sum())

    def _theta_in(self, i: int):
        """Call ``i``'s input parameters on the device."""
        if i == 0:
            return self._init(self._kp)
        if i == self.warm:
            return jax.device_put(self.after_warm)
        return jax.device_put(self.kept[i][0])

    def _update_errs(self) -> np.ndarray:
        """``(warm calls, pieces)``: ‖θ in of call i + 1 − the
        reference's AdamW of call i‖₂ over ‖that update‖₂, the reference's
        state carried from zero through the applied gradients."""
        errs, m, v = [], None, None
        for i in range(self.warm):
            g = self._applied(None, jax.device_put(self.kept[i][1]))
            theta = self._theta_in(i)
            if m is None:
                m = jax.tree.map(jnp.zeros_like, theta)
                v = jax.tree.map(jnp.zeros_like, theta)
            e, m, v = _update_err(theta, g, m, v, jnp.int32(i + 1),
                                  self._theta_in(i + 1), lr=self.lr)
            errs.append(np.asarray(e))
            del theta, g
        return np.stack(errs)

    def compare(self) -> dict:
        """The numbers compared.  A piece is one layer's parameter, and
        one expert's where it has an expert axis: the router, a norm or
        one expert's weights weigh as much as the embedding.

        * ``unresolved_gap``: Σ over EVERY call of |program's unresolved
          count − the reference's peel|;
        * ``grad_err``: the median piece, over the checked calls' pieces,
          of ‖applied gradient − Σ_resolved g_i / K‖₂ over ‖Σ_resolved g_i
          / K‖₂, the reference's gradient at the call's own input
          parameters (the worst piece, one routed expert's weights,
          reads too near the control to tell the two apart);
        * ``grad_err_tree``: the widest, over the checked calls, of the
          same norm over the whole tree (a wrong piece shows here, where
          the median does not see it);
        * ``update_err``: the worst piece of :meth:`_update_errs` (1 where
          the parameters are left unchanged);
        * ``loss_err``: the widest |step's loss − reference's| over the
          reference's (the mean of all K shards' losses).
        """
        gap = sum(abs(n - self.reference_resolution(m)[1])
                  for m, n in zip(self.masks, self.unresolved))
        update = self._update_errs()
        grad, tree_err, loss_err = [], [0.0], [0.0]
        for i in sorted(self.kept):
            _, sums, loss = self.kept[i]
            params = self._theta_in(i)
            resolved, _ = self.reference_resolution(self.masks[i])
            b = self.batch(i)
            want_loss, want = ref.coded_grad(params, b["tokens"],
                                             b["labels"], resolved, self.rcfg)
            del params
            got = self._applied(None, jax.device_put(sums))
            grad.append(np.asarray(_piece_errs(got, want)))
            num, den = _sq_dist(got, want)
            tree_err.append(float(np.sqrt(num) / max(np.sqrt(den), 1e-30)))
            want_loss = float(want_loss)
            loss_err.append(abs(loss - want_loss) / abs(want_loss))
            del got, want
        grad = np.stack(grad)
        names = _piece_names(self._shapes)
        print(json.dumps({"compare": {
            "checked_calls": sorted(self.kept),
            "grad_err_median_by_call": np.median(grad, 1).tolist(),
            "grad_err_worst_by_call": grad.max(1).tolist(),
            "grad_err_worst_piece": names[int(np.argmax(grad.max(0)))],
            "grad_err_tree_by_call": tree_err[1:],
            "update_err_by_call": update.max(1).tolist(),
            "update_err_worst_piece": names[int(np.argmax(
                update.max(0)))]}}), file=sys.stderr, flush=True)
        # np.median and np.max keep a NaN, which the harness reads as over
        # the limit
        return {"unresolved_gap": gap, "grad_err": float(np.median(grad)),
                "grad_err_tree": float(np.max(tree_err)),
                "update_err": float(np.max(update)),
                "loss_err": float(np.max(loss_err))}


def _lead(path) -> int:
    """How many leading axes of a parameter index its pieces: a stacked
    block's layer axis, then a routed experts' stack's expert axis."""
    keys = [getattr(k, "key", None) for k in path]
    return (keys[0] == "blocks") + ("experts" in keys)


def _piece_names(shapes) -> list:
    return [f"{jax.tree_util.keystr(path)}[{k}]"
            for path, a in jax.tree_util.tree_leaves_with_path(shapes)
            for k in range(int(np.prod(a.shape[:_lead(path)])))]


@jax.jit
def _piece_errs(got, want, base=None):
    """‖got − want‖₂ / ‖want − base‖₂ (``base`` 0 where None) of every
    piece of parameter trees, in :func:`_piece_names` order."""
    bases = [None] * len(jax.tree.leaves(want)) if base is None else \
        jax.tree.leaves(base)
    out = []
    for (path, g), w, b in zip(jax.tree_util.tree_leaves_with_path(got),
                               jax.tree.leaves(want), bases):
        n = int(np.prod(g.shape[:_lead(path)]))
        d = w if b is None else w - b
        num = jnp.sum(jnp.square(g - w).reshape(n, -1), axis=1)
        den = jnp.sum(jnp.square(d).reshape(n, -1), axis=1)
        out.append(jnp.sqrt(num / jnp.maximum(den, 1e-30)))
    return jnp.concatenate(out)


@jax.jit
def _sq_dist(got, want):
    """``(‖got − want‖², ‖want‖²)`` over two gradient trees."""
    sq = lambda t: sum(jnp.sum(x * x) for x in jax.tree.leaves(t))  # noqa
    return sq(jax.tree.map(jnp.subtract, got, want)), sq(want)


@partial(jax.jit, static_argnames="lr", donate_argnums=(2, 3))
def _update_err(theta, g, m, v, step, theta_next, *, lr: float):
    """``(per-piece update errors, m, v)`` of one reference AdamW step
    from ``theta`` against the program's ``theta_next``."""
    new, m, v = ref.adamw(theta, g, m, v, step, lr=lr)
    return _piece_errs(theta_next, new, theta), m, v
