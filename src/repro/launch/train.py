"""Training launcher.

Runs one architecture end-to-end on local devices: real data pipeline,
optimizer, checkpoints, watchdog. Without ``--reduced`` the published
config is trained; ``--reduced`` swaps in the family-preserving smoke
config that CPU runs use. The full production configs are also exercised
via the dry-run (launch/dryrun.py), which lowers this same step function
against the 16x16 / 2x16x16 meshes.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --reduced --steps 100 --batch 8 --seq 128 --ckpt-dir ckpt
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models.lm import LM
from repro.optim import OptConfig
from repro.train import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", choices=configs.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="the family-preserving smoke config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log", default=None, help="JSONL metrics path")
    ap.add_argument("--quantize-opt", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    model = LM(cfg)
    data = SyntheticLM(vocab=cfg.vocab, seq=args.seq,
                       global_batch=args.batch)
    tcfg = TrainConfig(
        opt=OptConfig(lr=args.lr, quantize_state=args.quantize_opt),
        microbatches=args.microbatches,
        warmup_steps=max(1, args.steps // 10), total_steps=args.steps)
    trainer = Trainer(model, data, tcfg, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, log_path=args.log)
    params, _, step = trainer.run(args.steps, key=jax.random.PRNGKey(0))
    losses = [m["loss"] for m in trainer.metrics_log if "loss" in m]
    print(f"[train] {args.arch}{' reduced' if args.reduced else ''}: "
          f"step {step}, "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
          f"stragglers={trainer.watchdog.straggler_steps}")


if __name__ == "__main__":
    main()
