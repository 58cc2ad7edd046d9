"""Quickstart: fine-tune a frozen transformer with a single global MetaTT
adapter and compare against LoRA at the same rank (paper Table 1 in
miniature — synthetic data, CPU-sized model).

    PYTHONPATH=src python examples/quickstart.py [--steps 80]
"""
import argparse

import numpy as np

from repro import configs as registry
from repro.config.base import OptimizerConfig, RunConfig, SHAPES, TrainConfig
from repro.data import LMStream
from repro.launch import compile_cache
from repro.peft import api as peft_api
from repro.train.trainer import Trainer


def train_one(adapter_kind: str, steps: int):
    cfg = registry.get_smoke_config("roberta-base")
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind=adapter_kind, adapter_rank=4,
                    adapter_alpha=4.0,
                    optimizer=OptimizerConfig(lr=2e-2, warmup_ratio=0.1),
                    train=TrainConfig(remat="none", seed=42))
    data = LMStream(vocab_size=cfg.vocab_size, seq_len=32, batch=8, seed=5,
                    branching=2)
    tr = Trainer(run=run, data=data, total_steps=steps)
    tr.train()
    n = peft_api.count_trainable(tr.spec, tr.state.adapter)
    return tr, n


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    args = ap.parse_args()
    print("== MetaTT (one global TT for ALL layers) ==")
    tr_tt, n_tt = train_one("metatt", args.steps)
    print("== LoRA (per-matrix A·B) ==")
    tr_lora, n_lora = train_one("lora", args.steps)

    def curve(tr):
        l = tr.losses()
        return l[0], float(np.mean(l[-5:]))

    l0, l1 = curve(tr_tt)
    print(f"\nMetaTT : {n_tt:6d} trainable params | loss {l0:.3f} -> {l1:.3f}")
    l0, l1 = curve(tr_lora)
    print(f"LoRA   : {n_lora:6d} trainable params | loss {l0:.3f} -> {l1:.3f}")
    print(f"\ncompression: {n_lora / n_tt:.1f}x fewer trainable parameters "
          f"(paper: up to 20x at RoBERTa scale)")


if __name__ == "__main__":
    main()
