"""The seam between the benchmark and the program under test (``repro``).

Everything the benchmark asks of the program goes through here: its model
configuration, the weights laid out as the program keeps them, the serving
engine and the trainer. The benchmark's own weights (``bench.lib.weights``)
are handed over; the program makes none.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp

from bench.lib import weights as W


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.config.base import ModelConfig
    m = W.dims(cfg)
    if "layer_norm_eps" in cfg:
        norm_kind, eps = "layernorm", cfg["layer_norm_eps"]
    else:
        norm_kind, eps = "rmsnorm", cfg["rms_norm_eps"]
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{cfg['name']}: hidden_act {cfg['hidden_act']!r}")
    unsupported = {k: cfg[k] for k, ok in (
        ("partial_rotary_factor", 1.0), ("use_qkv_bias", False),
        ("qk_layernorm", False), ("use_parallel_residual", False))
        if cfg.get(k, ok) != ok}
    if unsupported:
        raise ValueError(f"{cfg['name']}: the program cannot run "
                         f"{unsupported}; state what runs under runs_as")
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=m["L"],
        d_model=m["d"], num_heads=m["h"], num_kv_heads=m["kv"],
        d_ff=m["ff"], vocab_size=m["V"], head_dim=m["hd"],
        mlp="swiglu", norm_kind=norm_kind, norm_eps=eps,
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"])).validate()


def run_config(cfg: dict, adapter: dict, *, train: dict | None = None):
    """The program's RunConfig: the model, the adapter, and for training
    the optimizer and remat policy of the traffic file."""
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
        model=model_config(cfg),
        shape=SHAPES["train_4k" if train is not None else "decode_32k"],
        adapter_kind="metatt", adapter_variant=adapter["variant"],
        adapter_rank=adapter["rank"], adapter_alpha=adapter["alpha"],
        adapter_matrices=tuple(adapter["matrices"]),
        num_tasks=adapter.get("tasks", 0), kernels=KernelConfig(), **kw)


def _norm(cfg: dict, n: int):
    d = cfg["hidden_size"]
    if "layer_norm_eps" in cfg:                # gain 1, bias 0
        return {"w": jnp.ones((n, d), jnp.float32),
                "b": jnp.zeros((n, d), jnp.float32)}
    return {"w": jnp.zeros((n, d), jnp.float32)}   # the program keeps 1 + w


def params(cfg: dict, adapter: dict, seed: int) -> dict:
    """{"base", "adapter", "frozen"} in the program's layout, made on the
    device from ``seed`` in one jitted call."""
    L = cfg["num_hidden_layers"]

    def make(key):
        w = W.stacked(cfg, adapter, key)
        m = w["layers"]
        block = {"norm1": _norm(cfg, L),
                 "mixer": {k: m[k] for k in ("wq", "wk", "wv", "wo")},
                 "norm2": _norm(cfg, L),
                 "ffn": {k: m[k] for k in ("wg", "wu", "wd")}}
        base = {"embed": {"tok": w["embed"]}, "blocks": [block],
                "final_norm": _norm(cfg, 1)}
        return {"base": base, "adapter": {"cores": w["cores"]},
                "frozen": {}}

    return jax.jit(make)(W.seed_key(seed))


@contextlib.contextmanager
def weights_given(p: dict):
    """While open, the program's own parameter initialiser returns ``p``
    instead of making weights: the Trainer offers no way to take weights,
    and making its own first would hold two copies on the chip."""
    import repro.models.model as model_lib
    with mock.patch.object(model_lib, "init_params",
                           lambda cfg, spec, key: p):
        yield


def engine(cfg: dict, adapter: dict, p: dict, eng_cfg: dict):
    from repro.config.base import ServeConfig
    from repro.models import model as model_lib
    from repro.serving import AdapterRuntime, Engine
    run = run_config(cfg, adapter)
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


def trainer(cfg: dict, adapter: dict, p: dict, train: dict, feed):
    from repro.train.trainer import Trainer
    run = run_config(cfg, adapter, train=train)
    with weights_given(p):
        return Trainer(run=run, data=feed,
                       total_steps=train["optimizer"]["total_steps"])


def statuses():
    from repro.serving import engine as eng_mod
    return dict(finished=eng_mod.FINISHED, cancelled=eng_mod.CANCELLED,
                timeout=eng_mod.TIMEOUT, failed=eng_mod.FAILED)
