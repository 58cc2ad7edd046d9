"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-7b \
        --adapter metatt --rank 8 --steps 100 --ckpt-dir /tmp/run1

On this CPU container the launcher trains the reduced (smoke) config; on a
real TPU slice it would be invoked once per host under the production mesh
(``--mesh single|multi`` selects it; the dry-run validates those programs —
repro.launch.dryrun). The trainer provides checkpoint/auto-resume, the
straggler watchdog, DMRG rank schedules and gradient compression.
"""
from __future__ import annotations

import argparse

from repro import configs as registry
from repro.config.base import OptimizerConfig, RunConfig, SHAPES, TrainConfig
from repro.core.dmrg import RankSchedule
from repro.data import LMStream
from repro.launch import compile_cache
from repro.train.trainer import Trainer


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=list(registry.ALL_IDS))
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--adapter", default="metatt",
                    choices=("metatt", "lora", "vera", "lotr", "none"))
    ap.add_argument("--variant", default="4d",
                    choices=("4d", "5d", "4+1d", "4+ed"))
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=4.0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", default="none",
                    choices=("none", "int8", "topk"))
    ap.add_argument("--dmrg-start-rank", type=int, default=0,
                    help="enable DMRG schedule from this rank down to --rank")
    ap.add_argument("--steps-per-epoch", type=int, default=0)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full assigned config (TPU-scale) instead "
                         "of the reduced smoke config")
    return ap


def main() -> None:
    args = build_argparser().parse_args()
    compile_cache.enable()
    cfg = (registry.get_config(args.arch) if args.full_config
           else registry.get_smoke_config(args.arch))
    start_rank = args.dmrg_start_rank or args.rank
    run = RunConfig(
        model=cfg, shape=SHAPES[args.shape], adapter_kind=args.adapter,
        adapter_variant=args.variant, adapter_rank=start_rank,
        adapter_alpha=args.alpha,
        optimizer=OptimizerConfig(lr=args.lr),
        train=TrainConfig(seed=args.seed, ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every,
                          grad_compression=args.grad_compression,
                          remat="none" if not args.full_config else "block"))
    sched = None
    if args.dmrg_start_rank and args.dmrg_start_rank > args.rank:
        sched = RankSchedule.linear(args.dmrg_start_rank, args.rank,
                                    start_epoch=1, every=1, step=2)
    data = LMStream(vocab_size=cfg.vocab_size, seq_len=32, batch=8,
                    seed=args.seed, branching=2)
    tr = Trainer(run=run, data=data, total_steps=args.steps,
                 steps_per_epoch=args.steps_per_epoch,
                 rank_schedule=sched,
                 on_metrics=lambda s, m: (
                     s % 10 == 0 and print(
                         f"step {s:5d} loss {m['loss']:.4f} "
                         f"lr {m['lr']:.2e} {m['step_time_s']*1e3:.0f}ms")))
    tr.train()


if __name__ == "__main__":
    main()
