"""The seam between the benchmark and the program under test (``repro``),
for every architecture.

Everything the benchmark asks of the program goes through here or through
the architecture's own ``seam.py`` (``bench/models/<architecture>/``), which
gives the program's model configuration and the weights laid out as the
program keeps them. Here: the run configuration, the serving engine and the
trainer. The benchmark's own weights are handed over; the program makes
none.
"""
from __future__ import annotations

import contextlib
from unittest import mock


def run_config(model, adapter: dict, *, train: dict | None = None):
    """The program's RunConfig: the model (the ModelConfig that the
    architecture's ``seam.model_config`` gives), the adapter, and for
    training the optimizer and remat policy of the traffic file."""
    from repro.config.base import (KernelConfig, OptimizerConfig,
                                   RunConfig, SHAPES, TrainConfig)
    kw = {}
    if train is not None:
        o = train["optimizer"]
        kw = dict(
            optimizer=OptimizerConfig(
                lr=o["lr"], betas=tuple(o["betas"]), eps=o["eps"],
                weight_decay=o["weight_decay"],
                warmup_ratio=o["warmup_ratio"], grad_clip=o["grad_clip"],
                schedule=o["schedule"]),
            train=TrainConfig(remat=train["remat"], ckpt_every=0,
                              log_every=0))
    return RunConfig(
        model=model,
        shape=SHAPES["train_4k" if train is not None else "decode_32k"],
        adapter_kind="metatt", adapter_variant=adapter["variant"],
        adapter_rank=adapter["rank"], adapter_alpha=adapter["alpha"],
        adapter_matrices=tuple(adapter["matrices"]),
        num_tasks=adapter.get("tasks", 0), kernels=KernelConfig(), **kw)


@contextlib.contextmanager
def weights_given(p: dict):
    """While open, the program's own parameter initialiser returns ``p``
    instead of making weights: the Trainer offers no way to take weights,
    and making its own first would hold two copies on the chip."""
    import repro.models.model as model_lib
    with mock.patch.object(model_lib, "init_params",
                           lambda cfg, spec, key: p):
        yield


def engine(model, adapter: dict, p: dict, eng_cfg: dict):
    from repro.config.base import ServeConfig
    from repro.models import model as model_lib
    from repro.serving import AdapterRuntime, Engine
    run = run_config(model, adapter)
    spec = model_lib.build_adapter_spec(run)
    rt = AdapterRuntime.build(eng_cfg.get("runtime", "live"), p["base"],
                              spec, p["adapter"], p["frozen"])
    sv = ServeConfig(max_batch=eng_cfg["max_batch"],
                     cache_len=eng_cfg["cache_len"],
                     out_cap=eng_cfg["out_cap"],
                     num_blocks=eng_cfg["num_blocks"])
    return Engine(run.model, rt, serve=sv)


def request(prompt, max_new: int, task: int, deadline_s: float, rid):
    from repro.serving import Request
    return Request(prompt, max_new, task=task, deadline_s=deadline_s,
                   request_id=rid)


def trainer(model, adapter: dict, p: dict, train: dict, feed):
    from repro.train.trainer import Trainer
    run = run_config(model, adapter, train=train)
    with weights_given(p):
        return Trainer(run=run, data=feed,
                       total_steps=train["optimizer"]["total_steps"])


def statuses():
    from repro.serving import engine as eng_mod
    return dict(finished=eng_mod.FINISHED, cancelled=eng_mod.CANCELLED,
                timeout=eng_mod.TIMEOUT, failed=eng_mod.FAILED)
