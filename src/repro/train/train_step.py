"""Jitted train / serve steps.

train_step: PEFT semantics — ``jax.value_and_grad`` over the adapter pytree
only; the frozen base weights are a non-differentiated argument (no grads,
no optimizer state, no master copy — the memory model that makes 1T-param
fine-tuning fit, DESIGN.md §4). Supports microbatch gradient accumulation
(lax.scan), remat-per-super-block, and optional gradient compression.

The serving helpers (make_prefill / make_serve_step) live in
repro.serving.engine, next to the continuous-batching Engine.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, OptimizerConfig, TrainConfig
from repro.distributed.compression import GradCompressor
from repro.kernels import dispatch as kernel_dispatch
from repro.models import model as model_lib
from repro.models import moe as moe_lib
from repro.optim import adamw
from repro.peft import api as peft_api


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TrainState:
    adapter: Any
    opt: adamw.AdamWState
    residual: Any          # top-k compression error feedback (or None)
    step: jnp.ndarray

    def tree_flatten(self):
        return (self.adapter, self.opt, self.residual, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_train_state(adapter, compressor: GradCompressor) -> TrainState:
    return TrainState(adapter=adapter, opt=adamw.init_state(adapter),
                      residual=compressor.init_residual(adapter),
                      step=jnp.zeros((), jnp.int32))


def reinit_after_dmrg(state: TrainState, new_adapter,
                      compressor: GradCompressor,
                      moments=None) -> TrainState:
    """Rank change: rebuild the optimizer state for the new core shapes.

    moments: optional ``(mu, nu)`` pytrees transported through the sweep
    (core/dmrg.py ``moments=``) — the warm path keeps Adam statistics AND
    the step counter across the resplit. Without them, fall back to the
    paper's §3.3 fresh re-initialization (which restarts bias correction).
    """
    if moments is not None:
        opt = adamw.carry_state(state.opt, *moments)
    else:
        opt = adamw.init_state(new_adapter)
    return TrainState(adapter=new_adapter, opt=opt,
                      residual=compressor.init_residual(new_adapter),
                      step=state.step)


def _join_microbatches(v):
    """A record stacked over microbatches (nmb, layers, tokens, ...) as
    one over the whole batch's tokens (layers, nmb·tokens, ...)."""
    v = jnp.moveaxis(v, 0, 1)
    return v.reshape(v.shape[:1] + (-1,) + v.shape[3:])


def make_train_step(cfg: ModelConfig, spec: peft_api.AdapterSpec,
                    opt_cfg: OptimizerConfig, train_cfg: TrainConfig,
                    total_steps: int, *, chunk: int = 0,
                    donate: bool = True, kernels=None) -> Callable:
    """Returns jitted fn(state, base, frozen, batch) -> (state, metrics).

    kernels: KernelConfig (or resolved KernelPolicy) — routes the Eq. (5)
    hot path through the fused Pallas kernels (kernels/dispatch.py)."""
    schedule = adamw.make_schedule(opt_cfg, total_steps)
    compressor = GradCompressor(train_cfg.grad_compression)
    remat = train_cfg.remat != "none"
    policy = kernel_dispatch.resolve(kernels)

    def loss(adapter, base, frozen, batch):
        with jax.named_scope("forward"):
            return model_lib.loss_fn(adapter, base, frozen, batch, cfg,
                                     spec, remat=remat, chunk=chunk,
                                     policy=policy)

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def step_fn(state: TrainState, base, frozen, batch):
        # named scopes (repro.tracing) label each phase's device ops
        nmb = train_cfg.microbatch
        with jax.named_scope("gradient"):
            if nmb and nmb > 1:
                def micro(acc, mb):
                    (l, m), g = grad_fn(state.adapter, base, frozen, mb)
                    acc_g, acc_l = acc
                    return (jax.tree_util.tree_map(jnp.add, acc_g, g),
                            acc_l + l), m
                zero = (jax.tree_util.tree_map(
                    lambda a: jnp.zeros_like(a, jnp.float32),
                    state.adapter), jnp.zeros((), jnp.float32))
                mbs = jax.tree_util.tree_map(
                    lambda a: a.reshape((nmb, a.shape[0] // nmb)
                                        + a.shape[1:]), batch)
                (grads, lsum), ms = jax.lax.scan(micro, zero, mbs)
                grads = jax.tree_util.tree_map(lambda g: g / nmb, grads)
                loss_val = lsum / nmb
                metrics = {k: (_join_microbatches(v)
                               if k in moe_lib.RECORDS else jnp.mean(v))
                           for k, v in ms.items()}
            else:
                (loss_val, metrics), grads = grad_fn(state.adapter, base,
                                                     frozen, batch)
        with jax.named_scope("compression"):
            grads, residual = compressor(grads, state.residual)
        with jax.named_scope("optimizer"):
            lr = schedule(state.opt.step)
            new_adapter, new_opt, gnorm = adamw.update(
                grads, state.opt, state.adapter, opt_cfg, lr)
        new_state = TrainState(adapter=new_adapter, opt=new_opt,
                               residual=residual, step=state.step + 1)
        metrics = dict(metrics, loss=loss_val, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    return jax.jit(step_fn, donate_argnums=(0,) if donate else ())


def make_full_ft_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                      train_cfg: TrainConfig, total_steps: int) -> Callable:
    """Full fine-tuning baseline (paper Table 1 "FT" row): differentiates the
    base weights. fn(base, opt_state, batch) -> (base, opt_state, metrics)."""
    schedule = adamw.make_schedule(opt_cfg, total_steps)
    spec = peft_api.NONE

    def loss(base, batch):
        return model_lib.loss_fn({}, base, {}, batch, cfg, spec,
                                 remat=train_cfg.remat != "none")

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def step_fn(base, opt_state, batch):
        (loss_val, metrics), grads = grad_fn(base, batch)
        lr = schedule(opt_state.step)
        new_base, new_opt, gnorm = adamw.update(grads, opt_state, base,
                                                opt_cfg, lr)
        return new_base, new_opt, dict(metrics, loss=loss_val,
                                       grad_norm=gnorm)

    return jax.jit(step_fn, donate_argnums=(0, 1))
