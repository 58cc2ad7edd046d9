"""Multi-task learning with MetaTT-(4+1)D (paper §3.2 + App. B).

Pipeline: (1) "pre-train" the base on the MIXED task distribution (the three
tasks' rules conflict, so no frozen model solves all of them), (2) freeze it,
(3) joint-train ONE MetaTT-(4+1)D adapter whose task core disambiguates.

    PYTHONPATH=src python examples/multitask.py [--grad-heatmap]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as registry
from repro.config.base import OptimizerConfig, RunConfig, SHAPES, TrainConfig
from repro.data import ClassificationTasks
from repro.launch import compile_cache
from repro.models import model as M, transformer as T
from repro.optim import adamw
from repro.peft import api as peft_api
from repro.train import train_step as ts
from repro.train.trainer import Trainer


def core_grad_norms(tr, batch):
    """App. B heatmap: ||∇G||_F / sqrt(|G|) per TT core."""
    def loss(adapter):
        return M.loss_fn(adapter, tr.base, tr.frozen, batch, tr.cfg,
                         tr.spec)[0]
    g = jax.grad(loss)(tr.state.adapter)
    return [float(jnp.linalg.norm(c) / np.sqrt(c.size))
            for c in g["cores"]]


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--grad-heatmap", action="store_true")
    ap.add_argument("--pretrain-steps", type=int, default=150)
    ap.add_argument("--adapt-steps", type=int, default=240)
    args = ap.parse_args()

    cfg = registry.get_smoke_config("roberta-base")
    tasks = ClassificationTasks(vocab_size=cfg.vocab_size, seq_len=8,
                                batch=32, num_tasks=3, seed=9)
    key = jax.random.PRNGKey(0)

    print("[1/3] pre-training the base on mixed tasks (full FT)...")
    base = T.init_base_params(cfg, key)
    ft = ts.make_full_ft_step(cfg, OptimizerConfig(lr=3e-3,
                                                   warmup_ratio=0.05),
                              TrainConfig(remat="none"),
                              args.pretrain_steps)
    opt = adamw.init_state(base)
    for i in range(args.pretrain_steps):
        b = tasks.sample(i % 3)
        base, opt, m = ft(base, opt, {"tokens": jnp.asarray(b["tokens"]),
                                      "mask": jnp.asarray(b["mask"])})
    print(f"    pre-train loss: {float(m['loss']):.3f}")

    print("[2/3] freezing base; joint-training MetaTT-(4+1)D adapter...")
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_variant="4+1d",
                    adapter_rank=8, adapter_alpha=4.0, num_tasks=3,
                    optimizer=OptimizerConfig(lr=2e-2, warmup_ratio=0.05),
                    train=TrainConfig(remat="none", seed=42))
    tr = Trainer(run=run, data=tasks, total_steps=args.adapt_steps,
                 task_cycle=(0, 1, 2))
    tr.base = base
    tr.train()
    n = peft_api.count_trainable(tr.spec, tr.state.adapter)

    print("[3/3] evaluating per task...")
    bc, pl = peft_api.adapter_factors(tr.spec, tr.state.adapter, tr.frozen)
    accs = []
    for t in range(3):
        b = tasks.sample(t, split="eval")
        out = T.forward(base, cfg, tr.spec, bc, pl,
                        jnp.asarray(b["tokens"]), task=jnp.int32(t))
        acc = tasks.accuracy(np.asarray(out.logits[:, -2]), b["labels"],
                             tasks.class_token_base, tasks.n_classes)
        accs.append(acc)
        print(f"    task {t}: accuracy {acc:.3f}")
    print(f"\none adapter, {n} trainable params, "
          f"mean accuracy {np.mean(accs):.3f}")

    if args.grad_heatmap:
        b = tasks.sample(2)
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "mask": jnp.asarray(b["mask"]), "task": jnp.int32(2)}
        norms = core_grad_norms(tr, batch)
        names = ["G1(D)", "G2(L)", "G3(T)", "G4(M)", "G5(D)"]
        print("\nnormalized gradient per TT core (App. B heatmap, task 2):")
        for nm, v in zip(names, norms):
            print(f"    {nm:7s} {'#' * int(200 * v)} {v:.4f}")


if __name__ == "__main__":
    main()
