"""Training driver CLI.

Examples:
  # train any zoo arch (reduced preset for CPU, full for pods)
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \\
      --preset smoke --steps 50

  # the paper's Stage-1 encoder pre-training + triplet fine-tuning
  PYTHONPATH=src python -m repro.launch.train --arch semanticbbv-encoder \\
      --stage pretrain --steps 200

Restart safety: run under `python -m repro.train.fault_tolerance` supervision
or any cluster supervisor; SIGTERM checkpoints and exits 42; relaunch
resumes from the newest checkpoint on whatever device count exists.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import TrainConfig, get_arch, scaled_down
from repro.data.isa import stable_hash
from repro.models import build_model
from repro.train.trainer import Trainer
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.log import get_logger

log = get_logger("repro.launch.train")


def lm_batch_fn(vocab: int, batch: int, seq: int, cfg=None):
    def fn(step: int):
        r = np.random.RandomState(stable_hash("batch", step))
        out = {"tokens": jnp.asarray(
            r.randint(0, vocab, (batch, seq)), jnp.int32)}
        if cfg is not None and cfg.encoder_layers:
            out["frames"] = jnp.asarray(
                r.randn(batch, min(seq, 64), cfg.d_model), jnp.float32)
        if cfg is not None and cfg.frontend == "vision_patches":
            out["patches"] = jnp.asarray(
                r.randn(batch, cfg.num_prefix_embeddings, cfg.d_model),
                jnp.float32)
        return out

    return fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--stage", choices=["lm", "pretrain", "triplet"],
                    default="lm",
                    help="semanticbbv stages use the paper's objectives")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.preset == "smoke":
        cfg = scaled_down(cfg)
    model = build_model(cfg)
    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                     warmup_steps=max(2, args.steps // 20),
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=args.checkpoint_every)

    if args.stage == "lm":
        params, specs = model.init(jax.random.PRNGKey(0))

        def loss_fn(p, b):
            return model.loss(p, b, impl="ref")

        batch_fn = lm_batch_fn(cfg.vocab_size, args.batch, args.seq, cfg)
    else:
        # paper Stage-1 objectives on the synthetic BinaryCorp
        from repro.core.bbe import (
            BBEConfig, bbe_init, finetune_triplet_loss, pretrain_loss,
        )
        from repro.data.corpus import SyntheticBinaryCorp

        bcfg = BBEConfig() if args.preset == "full" else BBEConfig(
            dim_embeds=(48, 8, 8, 8, 8, 8), num_layers=2, num_heads=2,
            bbe_dim=64, max_len=64)
        corp = SyntheticBinaryCorp(n_functions=500, max_len=bcfg.max_len)
        params, specs = bbe_init(jax.random.PRNGKey(0), bcfg)
        if args.stage == "pretrain":
            def loss_fn(p, b):
                return pretrain_loss(p, bcfg, b["tokens"])

            def batch_fn(step):
                return {"tokens": jnp.asarray(
                    corp.pretrain_batch(step, args.batch)["tokens"])}
        else:
            def loss_fn(p, b):
                return finetune_triplet_loss(p, bcfg, b)

            def batch_fn(step):
                return {k: jnp.asarray(v) for k, v in
                        corp.triplet_batch(step, args.batch).items()}

    trainer = Trainer(loss_fn, params, specs, tc)
    trainer.install_preemption_handler()
    metrics = trainer.fit(batch_fn, args.steps)
    trainer.maybe_checkpoint(force=True)
    log.info("done: %s", {k: round(v, 4) for k, v in metrics.items()})


if __name__ == "__main__":
    main()
