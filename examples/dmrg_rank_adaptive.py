"""Rank-adaptive fine-tuning via DMRG-inspired sweeps (paper §3.3, Fig. 2).

Start at rank 10, intersperse Algorithm-1 sweeps after chosen epochs to walk
ranks down 10 -> 8 -> 6 -> 4 while AdamW keeps training (moments rebuilt
after each truncation, as the paper requires).

    PYTHONPATH=src python examples/dmrg_rank_adaptive.py
"""
import numpy as np

from repro import configs as registry
from repro.config.base import OptimizerConfig, RunConfig, SHAPES, TrainConfig
from repro.core import tt
from repro.core.dmrg import RankSchedule
from repro.data import LMStream
from repro.launch import compile_cache
from repro.peft import api as peft_api
from repro.train.trainer import Trainer


def main():
    compile_cache.enable()
    cfg = registry.get_smoke_config("roberta-base")
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_rank=10,
                    adapter_alpha=4.0,
                    optimizer=OptimizerConfig(lr=2e-2, warmup_ratio=0.1),
                    train=TrainConfig(remat="none", seed=42))
    data = LMStream(vocab_size=cfg.vocab_size, seq_len=32, batch=8, seed=5,
                    branching=2)
    steps_per_epoch = 15
    sched = RankSchedule(milestones=((1, 8), (2, 6), (3, 4)))
    tr = Trainer(run=run, data=data, total_steps=5 * steps_per_epoch,
                 steps_per_epoch=steps_per_epoch, rank_schedule=sched)

    ranks_log = []
    orig_metrics = tr.on_metrics
    def log(step, m):
        if step % steps_per_epoch == 0:
            ranks_log.append((step, tt.ranks(tr.state.adapter["cores"]),
                              peft_api.count_trainable(tr.spec,
                                                       tr.state.adapter)))
    tr.on_metrics = log
    tr.train()

    losses = tr.losses()
    print("\nrank trajectory (paper Fig. 2 arrows):")
    for step, ranks, n in ranks_log:
        print(f"    step {step:3d}: ranks={ranks} trainable={n}")
    print(f"final ranks: {tt.ranks(tr.state.adapter['cores'])}")
    print(f"loss: {losses[0]:.3f} -> {np.mean(losses[-5:]):.3f} "
          f"(fixed-rank-4 training from scratch would have "
          f"{'fewer' if True else ''} params the whole time but the paper "
          f"shows the high->low schedule reaches better optima)")


if __name__ == "__main__":
    main()
