"""Training loop with optional LDPC-coded gradient aggregation.

Two gradient paths:
  * plain      — standard jit'd value_and_grad (the mesh's data axis
                 all-reduces gradients; on one CPU device this is just SGD).
  * coded_agg  — the paper's insight applied to ANY loss (grad_agg.py):
                 the batch is split into K shards, per-shard gradients are
                 the systematic symbols of an LDGM code, parity "workers"
                 hold small shard unions, a straggler mask erases worker
                 symbols, and the master peels for D rounds.  Unresolved
                 shards are zero-filled => unbiased (1-q_D)-scaled gradient
                 (Lemma 1 verbatim).  The reduce runs layer by layer, one
                 gradient bucket at a time (train/coded_step.py).

This is the runnable CPU-scale driver (examples/train_llm.py); the
production-mesh path is exercised by launch/dryrun.py.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp

from repro.core.grad_agg import CodedAggregator
from repro.core.straggler import BernoulliStragglers
from repro.models import Model
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.train.checkpoint import save_checkpoint
from repro.train.coded_step import CodedTrainStep

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0              # 0 = no checkpoints
    ckpt_dir: str = "checkpoints"
    opt: AdamWConfig = AdamWConfig()
    # coded aggregation
    coded_agg: bool = False
    n_shards: int = 8
    redundancy: float = 0.5
    row_weight: int = 4
    decode_iters: int = 8
    decode_backend: str = "auto"  # dense|sparse|pallas|pallas_tiled|auto (decoder.py)
    straggler_q0: float = 0.0


class Trainer:
    def __init__(self, model: Model, tcfg: TrainerConfig):
        self.model = model
        self.tcfg = tcfg
        self.agg = (CodedAggregator.build(
            tcfg.n_shards, redundancy=tcfg.redundancy,
            row_weight=tcfg.row_weight, decode_iters=tcfg.decode_iters,
            decode_backend=tcfg.decode_backend)
            if tcfg.coded_agg else None)
        self.straggler = BernoulliStragglers(tcfg.straggler_q0)
        self._step_fn = self._build_step()

    def _build_step(self):
        model, tcfg, agg = self.model, self.tcfg, self.agg

        if not tcfg.coded_agg:
            @jax.jit
            def step(params, opt_state, batch, key):
                loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
                params, opt_state = adamw_update(params, grads, opt_state, tcfg.opt)
                return params, opt_state, loss, jnp.int32(0)
            return step

        return CodedTrainStep(model, agg, tcfg.opt)

    def fit(self, params, batches: Iterator[dict], *, key=None,
            callback: Callable[[int, float], None] | None = None):
        key = key if key is not None else jax.random.PRNGKey(0)
        opt_state = adamw_init(params)
        history = []
        t0 = time.time()
        for step_i in range(self.tcfg.steps):
            batch = next(batches)
            key, k1 = jax.random.split(key)
            if self.tcfg.coded_agg:
                # the mask is drawn outside the step: the step takes the
                # workers that straggled as given
                mask = self.straggler.sample(k1, self.agg.n_workers)
                res = self._step_fn(params, opt_state, batch, mask)
                params, opt_state = res.params, res.opt_state
                loss, unresolved = res.loss, res.unresolved
            else:
                params, opt_state, loss, unresolved = self._step_fn(
                    params, opt_state, batch, k1)
            loss = float(loss)
            history.append(loss)
            if callback:
                callback(step_i, loss)
            if self.tcfg.log_every and step_i % self.tcfg.log_every == 0:
                dt = time.time() - t0
                print(f"step {step_i:5d}  loss {loss:8.4f}  "
                      f"unresolved {int(unresolved)}  ({dt:.1f}s)")
            if self.tcfg.ckpt_every and (step_i + 1) % self.tcfg.ckpt_every == 0:
                save_checkpoint(self.tcfg.ckpt_dir, step_i + 1, params, opt_state,
                                {"loss": loss})
        return params, opt_state, history
