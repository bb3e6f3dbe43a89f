"""Training launcher.

CPU-runnable driver over the architecture zoo (reduced or scaled dims) with
optional LDPC-coded gradient aggregation — the paper's technique as a
first-class training feature.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --steps 50 --coded-agg --straggler-q0 0.1

The full production configs are exercised via launch/dryrun.py (AOT
lower+compile on the placeholder meshes); this driver runs REAL steps.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import get_config, list_configs
from repro.data.batches import make_batch
from repro.models import Model
from repro.optim import AdamWConfig
from repro.train import Trainer, TrainerConfig


def batch_iterator(cfg, batch, seq, seed=0):
    key = jax.random.PRNGKey(seed)
    while True:
        key, k = jax.random.split(key)
        yield make_batch(cfg, batch, seq, key=k)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--coded-agg", action="store_true")
    ap.add_argument("--n-shards", type=int, default=8)
    ap.add_argument("--straggler-q0", type=float, default=0.0)
    ap.add_argument("--decode-iters", type=int, default=8)
    ap.add_argument("--decode-backend", default="auto",
                    choices=["auto", "dense", "sparse", "pallas", "pallas_tiled"],
                    help="LDPC decode implementation (see core/decoder.py)")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg, remat=False, attn_chunk=min(64, args.seq))
    params = model.init(jax.random.PRNGKey(0))
    print(f"arch={cfg.name} params={model.param_count(params):,} "
          f"active={model.active_param_count(params):,}")

    tcfg = TrainerConfig(
        steps=args.steps, log_every=args.log_every,
        ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
        opt=AdamWConfig(lr=args.lr),
        coded_agg=args.coded_agg, n_shards=args.n_shards,
        straggler_q0=args.straggler_q0, decode_iters=args.decode_iters,
        decode_backend=args.decode_backend,
    )
    trainer = Trainer(model, tcfg)
    batches = batch_iterator(cfg, args.batch, args.seq)
    params, _, history = trainer.fit(params, batches)
    print(f"final loss {history[-1]:.4f} (from {history[0]:.4f})")
    return history


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
