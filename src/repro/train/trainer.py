"""Training loop: checkpoint/restart, DMRG rank-adaptive sweeps, straggler
watchdog, multi-task cycling.

The loop is deliberately host-driven (the paper's §3.3 uses a custom loop for
the same reason: DMRG changes the *model shapes* mid-run, which no jitted
graph can do). Rank changes trigger: sweep (with AdamW moments transported
through each two-site resplit when ``train.dmrg_warm_moments`` — the
paper's cold re-init is the fallback) → re-place the rank-changed cores +
moments onto the ambient GSPMD mesh → automatic re-jit via new shapes.
Sweeps run BEFORE the boundary checkpoint and the applied schedule position
is recorded in checkpoint meta, so a resume lands on the post-sweep
(params, opt-state, schedule-position) triple instead of silently losing
the rank change.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.checkpoint import CheckpointManager
from repro.config.base import RunConfig
from repro.core import dmrg as dmrg_lib
from repro.core import tt
from repro.distributed import FailureInjector, GradCompressor, Watchdog
from repro.sharding import rules
from repro.models import model as model_lib
from repro.peft import api as peft_api
from repro.train import train_step as ts


@dataclasses.dataclass
class Trainer:
    run: RunConfig
    data: Any                                  # iterator with state()/restore()
    total_steps: int
    steps_per_epoch: int = 0                   # 0 -> no epoch semantics
    rank_schedule: Optional[dmrg_lib.RankSchedule] = None
    failure_injector: Optional[FailureInjector] = None
    on_metrics: Optional[Callable[[int, dict], None]] = None
    eval_fn: Optional[Callable[[Any], dict]] = None
    task_cycle: tuple = ()                     # MTL: task ids for joint training

    def __post_init__(self):
        run = self.run
        self.cfg = run.model
        self.spec = model_lib.build_adapter_spec(run)
        key = jax.random.PRNGKey(run.train.seed)
        params = model_lib.init_params(self.cfg, self.spec, key)
        self.base, self.frozen = params["base"], params["frozen"]
        self.compressor = GradCompressor(run.train.grad_compression)
        self.state = ts.init_train_state(params["adapter"], self.compressor)
        self.step_fn = ts.make_train_step(
            self.cfg, self.spec, run.optimizer, run.train, self.total_steps,
            kernels=run.kernels)
        self.ckpt = (CheckpointManager(run.train.ckpt_dir,
                                       keep=run.train.ckpt_keep)
                     if run.train.ckpt_dir else None)
        self.watchdog = Watchdog()
        self.straggler_events: list = []
        self.watchdog.on_straggler = lambda s, dt, ew: \
            self.straggler_events.append((s, dt, ew))
        self.history: list = []
        self._dmrg_applied: list = []      # epochs whose sweep already ran
        self._resume()

    # ------------------------------------------------------------------
    def _resume(self) -> None:
        if self.ckpt is None:
            return
        got = self.ckpt.restore_latest(self.state)
        if got is None:
            return
        step, state, meta = got
        self.state = state
        if "data_state" in meta and hasattr(self.data, "restore"):
            self.data.restore(meta["data_state"])
        dm = meta.get("dmrg") or {}
        self._dmrg_applied = list(dm.get("applied_epochs", []))
        extra = (f" (dmrg epochs {self._dmrg_applied}, "
                 f"ranks {tuple(dm.get('ranks', ()))})" if dm else "")
        print(f"[trainer] resumed from checkpoint step {step}{extra}")

    def _save(self, step: int) -> None:
        if self.ckpt is None:
            return
        meta = {}
        if hasattr(self.data, "state"):
            meta["data_state"] = self.data.state()
        adapter = self.state.adapter
        if isinstance(adapter, dict) and "cores" in adapter:
            # schedule position rides with the reshaped params/opt-state so
            # a resume can't silently lose a rank change
            meta["dmrg"] = {
                "applied_epochs": list(self._dmrg_applied),
                "ranks": [int(r) for r in tt.ranks(adapter["cores"])],
            }
        with tracing.span("trainer.checkpoint", step=step):
            self.ckpt.save(step, self.state, meta)

    # ------------------------------------------------------------------
    def _maybe_dmrg(self, step: int) -> None:
        """End-of-epoch DMRG sweep per the rank schedule (paper Fig. 2)."""
        if (self.rank_schedule is None or not self.steps_per_epoch
                or self.spec.kind != "metatt"):
            return
        if step == 0 or step % self.steps_per_epoch:
            return
        epoch = step // self.steps_per_epoch
        target = self.rank_schedule.rank_after_epoch(epoch)
        if target is None or epoch in self._dmrg_applied:
            return
        warm = self.run.train.dmrg_warm_moments
        moments = (self.state.opt.mu, self.state.opt.nu) if warm else None
        with tracing.span("trainer.dmrg", step=step):
            res = dmrg_lib.dmrg_sweep(self.state.adapter,
                                      target_rank=target, moments=moments)
            n_before = peft_api.count_trainable(self.spec,
                                                self.state.adapter)
            n_after = peft_api.count_trainable(self.spec, res.params)
            self.state = ts.reinit_after_dmrg(self.state, res.params,
                                              self.compressor,
                                              moments=res.moments)
            # the host-side resplit left stale placements: put the
            # rank-changed cores + moments back onto the ambient mesh
            # before the retrace
            self.state = rules.reshard_after_reshape(self.state)
        self._dmrg_applied.append(epoch)
        print(f"[trainer] DMRG sweep @step {step}: ranks -> {res.ranks} "
              f"params {n_before} -> {n_after} "
              f"({'warm' if warm else 'cold'} moments)")

    # ------------------------------------------------------------------
    def _next_batch(self, step: int) -> dict:
        if self.task_cycle:
            task = self.task_cycle[step % len(self.task_cycle)]
            raw = self.data.sample(task)
        else:
            raw = next(self.data)
        return {k: jnp.asarray(v) for k, v in raw.items()
                if k in ("tokens", "mask", "task", "embeds", "enc_embeds")}

    def train(self, steps: Optional[int] = None) -> list:
        steps = steps or self.total_steps
        start = int(self.state.step)
        for step in range(start, steps):
            with tracing.step_span("trainer.step", step):
                self._step(step)
        if self.ckpt is not None:
            self._save(steps)
            self.ckpt.wait()
        return self.history

    def _step(self, step: int) -> None:
        """One training step, each phase under its ``repro.tracing``
        span."""
        if self.failure_injector is not None:
            self.failure_injector.check(step)
        with tracing.span("trainer.batch"):
            batch = self._next_batch(step)
        t0 = time.perf_counter()
        with tracing.span("trainer.dispatch"):
            self.state, metrics = self.step_fn(self.state, self.base,
                                               self.frozen, batch)
        with tracing.span("trainer.wait"):
            metrics = {k: float(v) if np.ndim(v) == 0 else np.asarray(v)
                       for k, v in metrics.items()}
        dt = time.perf_counter() - t0
        self.watchdog.step(step, dt)
        metrics["step_time_s"] = dt
        self.history.append((step, metrics))
        if self.on_metrics is not None:
            self.on_metrics(step, metrics)
        # sweep BEFORE the boundary checkpoint: a save at an epoch edge
        # must capture the post-sweep triple, or a resume from it would
        # silently lose the rank change
        self._maybe_dmrg(step + 1)
        if self.run.train.ckpt_every and \
                (step + 1) % self.run.train.ckpt_every == 0:
            self._save(step + 1)

    # ------------------------------------------------------------------
    def losses(self) -> np.ndarray:
        return np.array([m["loss"] for _, m in self.history])
