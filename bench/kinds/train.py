"""A training cell: ``Trainer.train`` stepping a MetaTT adapter on a frozen
base, one step per call, for ``--seconds``.

Set-up builds the Trainer (weights from the seed, made by the cell's
architecture and handed to the program), and drives it through its first
steps with the window's own call and feed; those steps compile the step and
are the ones the reference follows. The window then keeps stepping the same
Trainer. The rate counts every token of
every step in the window over the window's whole wall time, batch making
included.

``correct``: the reference (float32, layer by layer) follows the first
steps with the same batches and the same AdamW; ``bench.lib.compare`` says
what is compared.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import cells, compare, peaks, program, report, tracing
from bench.lib.report import log as _log
from bench.lib import traffic as traffic_lib


def _leaves(tree):
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def first_steps(tr, steps: int, beta1: float) -> dict:
    """Drive ``tr`` through its first ``steps`` steps; read the losses, the
    first gradient (from Adam's first moment after step 1) and the
    parameters' change."""
    start = _leaves(tr.state.adapter)
    tr.train(1)
    grads = [m / (1.0 - beta1) for m in _leaves(tr.state.opt.mu)]
    tr.train(steps)
    change = [a - b for a, b in zip(_leaves(tr.state.adapter), start)]
    losses = [m["loss"] for _, m in tr.history[:steps]]
    return {"losses": losses, "grads": grads, "change": change,
            "start": start}


def feed_for(cfg, traffic, seed, **kw):
    d = traffic["data"]
    return traffic_lib.MarkovFeed(cfg["vocab_size"], d["seq_len"],
                                  d["batch"], seed,
                                  branching=d["branching"], **kw)


def run(cell, args, t_start: float, devs) -> dict:
    cfg, traffic = cell.config, cell.traffic
    adapter, train = traffic["adapter"], traffic["train"]
    seed, steps = args.seed, traffic["check"]["steps"]
    d = traffic["data"]
    arch = cells.architecture(cell)
    t_in = time.perf_counter()

    p = arch.seam.params(cfg, adapter, seed)
    feed = feed_for(cfg, traffic, seed,
                    on_batch=lambda: tracing.span("make_batch"))
    tr = program.trainer(arch.seam.model_config(cfg), adapter, p, train,
                         feed)
    del p
    t_tr = time.perf_counter()
    prog = first_steps(tr, steps, train["optimizer"]["betas"][0])
    setup_s = time.perf_counter() - t_start
    _log(f"set-up {setup_s:.2f}s: start to cell {t_in - t_start:.2f}s, "
         f"weights and trainer {t_tr - t_in:.2f}s, first steps "
         f"{setup_s - (t_tr - t_start):.2f}s")
    _log(f"first {steps} steps: losses {prog['losses']}, step times "
         f"{[round(m['step_time_s'], 3) for _, m in tr.history]}")

    n0 = len(tr.history)
    t0 = time.perf_counter()
    while True:
        with tracing.span("train_step"):
            tr.train(int(tr.state.step) + 1)
        if time.perf_counter() - t0 >= args.seconds:
            break
    wall = time.perf_counter() - t0
    done = tr.history[n0:]
    tok = len(done) * d["batch"] * d["seq_len"]
    failed = sum(not np.isfinite(m["loss"]) for _, m in done)
    _log(f"window {wall:.2f}s: {len(done)} steps, step times "
         f"{[round(m['step_time_s'], 3) for _, m in done]}")

    def step_work(k: int) -> dict:
        """``k`` steps' work: one step's, from the shapes and the counters
        of the program's last call, times ``k``."""
        one = arch.work.train_step(cfg, adapter, d["batch"], d["seq_len"],
                                   arch.seam.counts(tr))
        return {key: v * k for key, v in one.items()}

    e2e = {"train_tokens_per_s": tok / wall, "setup_s": setup_s}
    ctx = {"kind": "train", "cfg": cfg, "traffic": traffic,
           "peak": peaks.peak(devs[0].device_kind),
           "device_kind": devs[0].device_kind,
           "window": {"seconds": wall, "tokens": tok, "steps": len(done),
                      "work": step_work(len(done))}}
    breakdown = None
    if args.trace:
        k = traffic["trace"]["steps"]
        with tracing.profiled(("train_step", "make_batch")) as held:
            for _ in range(k):
                with tracing.span("train_step"):
                    tr.train(int(tr.state.step) + 1)
        ctx["trace"] = held.trace
        ctx["traced"] = {"work": step_work(k)}
        breakdown = tracing.breakdown(held.trace)
    device = report.device_info(devs, cell.chips)
    if args.trace:
        device["busy_s"] = ctx["trace"].busy_s()
        device["window_s"] = ctx["trace"].window_s
    del tr, feed
    gc.collect()
    _log(f"device bytes in use before the reference "
         f"{report.bytes_in_use(devs[0])}")

    ref = arch.Reference(cfg, adapter, seed)
    same = all(np.array_equal(a, np.asarray(b))
               for a, b in zip(prog["start"], ref.cores()))
    t0 = time.perf_counter()
    batches = [feed_for(cfg, traffic, seed).batch_at(i) for i in range(steps)]
    want = compare.reference_steps(ref, batches, train["optimizer"], steps)
    _log(f"reference {time.perf_counter() - t0:.1f}s: losses "
         f"{want['losses']}; start cores identical {same}")
    got = compare.train_checks(prog, want)
    if not same:
        got = {k: float("inf") for k in got}
    checks = {k: {"value": got[k], "limit": lim}
              for k, lim in cell.limits.items()}
    return {"correct": report.checks_ok(checks), "attempted": len(done),
            "failed": failed, "e2e": e2e, "ctx": ctx, "device": device,
            "checks": checks, "breakdown": breakdown,
            "sample": (batches, want), "details": compare.details(prog, want)}
