"""End-to-end training driver: any assigned architecture (reduced config on
CPU), MetaTT adapter, synthetic data, checkpointing + auto-resume.

    PYTHONPATH=src python examples/train_e2e.py --arch gemma-7b --steps 100
    # kill it mid-run, run the same command again -> resumes from the
    # latest checkpoint with identical data order.
"""
import argparse

import numpy as np

from repro import configs as registry
from repro.config.base import OptimizerConfig, RunConfig, SHAPES, TrainConfig
from repro.data import LMStream
from repro.launch import compile_cache
from repro.peft import api as peft_api
from repro.train.trainer import Trainer


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b",
                    choices=list(registry.ARCH_IDS) + ["roberta-base"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--adapter", default="metatt",
                    choices=("metatt", "lora", "vera", "lotr"))
    ap.add_argument("--variant", default="4d")
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    ap.add_argument("--grad-compression", default="none",
                    choices=("none", "int8", "topk"))
    args = ap.parse_args()

    cfg = registry.get_smoke_config(args.arch)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind=args.adapter, adapter_variant=args.variant,
                    adapter_rank=args.rank, adapter_alpha=4.0,
                    optimizer=OptimizerConfig(lr=1e-2, warmup_ratio=0.06),
                    train=TrainConfig(remat="none", seed=42,
                                      ckpt_dir=args.ckpt_dir, ckpt_every=20,
                                      grad_compression=args.grad_compression))
    data = LMStream(vocab_size=cfg.vocab_size, seq_len=32, batch=8, seed=5,
                    branching=2)
    tr = Trainer(run=run, data=data, total_steps=args.steps)
    n = peft_api.count_trainable(tr.spec, tr.state.adapter)
    print(f"arch={args.arch} adapter={args.adapter}-{args.variant} "
          f"rank={args.rank} trainable={n}")
    tr.train()
    losses = tr.losses()
    if len(losses):
        print(f"loss: {losses[0]:.3f} -> {np.mean(losses[-5:]):.3f} "
              f"over {len(losses)} steps (resumed runs show only new steps)")
    if tr.straggler_events:
        print(f"straggler watchdog events: {tr.straggler_events}")
    print(f"checkpoints in {args.ckpt_dir}: {tr.ckpt.all_steps()}")


if __name__ == "__main__":
    main()
