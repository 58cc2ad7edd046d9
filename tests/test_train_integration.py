"""Integration tests: the trainer loop end-to-end on CPU.

Covers: loss decreases, checkpoint/restart resume equivalence, simulated
node failure + auto-resume, DMRG rank-adaptive training, gradient
compression, microbatch accumulation equivalence, full-FT baseline.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as registry
from repro.config.base import (OptimizerConfig, RunConfig, SHAPES,
                               TrainConfig)
from repro.core.dmrg import RankSchedule
from repro.data import LMStream
from repro.distributed import FailureInjector, SimulatedFailure
from repro.train.trainer import Trainer

CFG = registry.get_smoke_config("stablelm-1.6b")


def _run(tmp, steps=24, seed=3, **kw):
    run = RunConfig(
        model=CFG, shape=SHAPES["train_4k"], adapter_kind="metatt",
        adapter_rank=kw.pop("adapter_rank", 4), adapter_alpha=4.0,
        optimizer=OptimizerConfig(lr=2e-2, warmup_ratio=0.1),
        train=TrainConfig(seed=seed, ckpt_every=kw.pop("ckpt_every", 0),
                          ckpt_dir=kw.pop("ckpt_dir", ""),
                          remat="none",
                          grad_compression=kw.pop("grad_compression",
                                                  "none"),
                          microbatch=kw.pop("microbatch", 0),
                          dmrg_warm_moments=kw.pop("dmrg_warm_moments",
                                                   True)))
    data = LMStream(vocab_size=CFG.vocab_size, seq_len=32, batch=8,
                    seed=11, branching=2)
    return Trainer(run=run, data=data, total_steps=steps, **kw)


def test_loss_decreases(tmp_path):
    tr = _run(tmp_path, steps=30)
    tr.train()
    losses = tr.losses()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses


def test_checkpoint_resume_is_equivalent(tmp_path):
    d = str(tmp_path / "ck")
    # uninterrupted run
    tr_full = _run(tmp_path, steps=20)
    tr_full.train()
    # interrupted at step 10 by a simulated node failure, then restarted
    tr_a = _run(tmp_path, steps=20, ckpt_dir=d, ckpt_every=5,
                failure_injector=FailureInjector(fail_at_step=10))
    with pytest.raises(SimulatedFailure):
        tr_a.train()
    tr_b = _run(tmp_path, steps=20, ckpt_dir=d, ckpt_every=5)
    assert int(tr_b.state.step) == 10  # auto-resumed from latest snapshot
    tr_b.train()
    # identical final adapter: deterministic data + restored opt state
    la = tr_full.state.adapter["cores"]
    lb = tr_b.state.adapter["cores"]
    for x, y in zip(la, lb):
        np.testing.assert_allclose(x, y, atol=1e-5)


def test_dmrg_rank_adaptive_training(tmp_path):
    sched = RankSchedule(milestones=((1, 6), (2, 4)))
    tr = _run(tmp_path, steps=30, steps_per_epoch=10, rank_schedule=sched)
    # starting rank 8 per run config? adapter_rank=4 -> start higher
    tr.run = dataclasses.replace(tr.run, adapter_rank=8)
    tr2 = Trainer(run=dataclasses.replace(tr.run), data=tr.data,
                  total_steps=30, steps_per_epoch=10, rank_schedule=sched)
    tr2.train()
    from repro.core import tt
    final_ranks = tt.ranks(tr2.state.adapter["cores"])
    assert max(final_ranks) <= 4, final_ranks
    # optimizer moments were rebuilt to the new shapes
    for m, p in zip(jax.tree_util.tree_leaves(tr2.state.opt.mu),
                    jax.tree_util.tree_leaves(tr2.state.adapter)):
        assert m.shape == p.shape
    losses = tr2.losses()
    assert np.isfinite(losses).all()


def test_dmrg_warm_moments_carry_over(tmp_path):
    """Regression for the stale-moment bug: a rank-changed core must get
    moments RESPLIT with the bond (warm, transported through the sweep)
    and keep the Adam step counter — the old reinit silently zeroed both."""
    sched = RankSchedule(milestones=((1, 6),))
    tr = _run(tmp_path, steps=10, adapter_rank=8, steps_per_epoch=10,
              rank_schedule=sched)
    tr.train()          # sweep fires at the step-10 epoch boundary
    from repro.core import tt
    assert max(tt.ranks(tr.state.adapter["cores"])) <= 6
    # moments match the NEW core shapes (no stale-shape crash on step 11)
    for m, p in zip(jax.tree_util.tree_leaves(tr.state.opt.mu),
                    jax.tree_util.tree_leaves(tr.state.adapter)):
        assert m.shape == p.shape
    # warm: the transported first moments are non-trivial, second moments
    # stay non-negative, and the bias-correction clock did NOT rewind
    assert int(tr.state.opt.step) == 10
    mu_norm = sum(float(jnp.abs(m).sum())
                  for m in jax.tree_util.tree_leaves(tr.state.opt.mu))
    assert mu_norm > 0
    for v in jax.tree_util.tree_leaves(tr.state.opt.nu):
        assert float(v.min()) >= 0
    # the next step runs against the resplit moments without retracing pain
    tr.train(steps=11)
    assert np.isfinite(tr.losses()).all()
    # cold fallback (paper §3.3): fresh zeros, clock restarted
    tr_cold = _run(tmp_path, steps=10, adapter_rank=8, steps_per_epoch=10,
                   rank_schedule=sched, dmrg_warm_moments=False)
    tr_cold.train()
    assert int(tr_cold.state.opt.step) == 0
    assert sum(float(jnp.abs(m).sum()) for m in
               jax.tree_util.tree_leaves(tr_cold.state.opt.mu)) == 0


def test_dmrg_resume_lands_on_post_sweep_triple(tmp_path):
    """A checkpoint at an epoch boundary must capture the POST-sweep
    (params, opt-state, schedule-position) triple: resuming from it
    continues with the reshaped cores + carried moments and never replays
    the sweep (the old save-then-sweep order silently lost the rank
    change on restart)."""
    sched = RankSchedule(milestones=((1, 6),))
    kw = dict(adapter_rank=8, steps_per_epoch=10, rank_schedule=sched)
    # uninterrupted run
    tr_full = _run(tmp_path, steps=20, **kw)
    tr_full.train()
    # interrupted right after the boundary checkpoint, then restarted
    d = str(tmp_path / "ck")
    tr_a = _run(tmp_path, steps=20, ckpt_dir=d, ckpt_every=10,
                failure_injector=FailureInjector(fail_at_step=15), **kw)
    with pytest.raises(SimulatedFailure):
        tr_a.train()
    tr_b = _run(tmp_path, steps=20, ckpt_dir=d, ckpt_every=10, **kw)
    from repro.core import tt
    assert int(tr_b.state.step) == 10
    # the restored triple is post-sweep: reshaped cores, carried moments,
    # schedule position recorded so epoch 1 is never re-applied
    assert max(tt.ranks(tr_b.state.adapter["cores"])) <= 6
    assert int(tr_b.state.opt.step) == 10
    assert tr_b._dmrg_applied == [1]
    tr_b.train()
    for x, y in zip(tr_full.state.adapter["cores"],
                    tr_b.state.adapter["cores"]):
        np.testing.assert_allclose(x, y, atol=1e-5)


def test_dmrg_training_under_forced_mesh(tmp_path):
    """Rank-adaptive training under an ambient 4-device GSPMD mesh: the
    host-side sweep reshapes cores + moments, and the trainer re-places
    them on the mesh (sharding/rules.py::reshard_after_reshape) before the
    retrace. Subprocess with fake host devices, like test_sharding.py."""
    import subprocess
    import sys
    import textwrap
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    code = textwrap.dedent("""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro import configs as registry
        from repro.config.base import (OptimizerConfig, RunConfig, SHAPES,
                                       TrainConfig)
        from repro.core import tt
        from repro.core.dmrg import RankSchedule
        from repro.data import LMStream
        from repro.train.trainer import Trainer
        assert jax.device_count() == 4
        cfg = registry.get_smoke_config('stablelm-1.6b')
        run = RunConfig(model=cfg, shape=SHAPES['train_4k'],
                        adapter_kind='metatt', adapter_rank=8,
                        adapter_alpha=4.0,
                        optimizer=OptimizerConfig(lr=2e-2,
                                                  warmup_ratio=0.1),
                        train=TrainConfig(seed=3, remat='none'))
        data = LMStream(vocab_size=cfg.vocab_size, seq_len=32, batch=8,
                        seed=11, branching=2)
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2),
                    ('data', 'model'))
        with jax.set_mesh(mesh):
            tr = Trainer(run=run, data=data, total_steps=15,
                         steps_per_epoch=10,
                         rank_schedule=RankSchedule(milestones=((1, 6),)))
            tr.train()
        ranks = tt.ranks(tr.state.adapter['cores'])
        assert max(ranks) <= 6, ranks
        assert int(tr.state.opt.step) == 15
        # every rank-changed leaf actually lives on the 4-device mesh
        for leaf in jax.tree_util.tree_leaves(tr.state.adapter):
            assert len(leaf.devices()) == 4, leaf.sharding
        for leaf in jax.tree_util.tree_leaves(tr.state.opt.mu):
            assert len(leaf.devices()) == 4, leaf.sharding
        losses = np.array([m['loss'] for _, m in tr.history])
        assert np.isfinite(losses).all()
        print('OK', ranks, losses[-1])
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_grad_compression_trains(tmp_path):
    for kind in ("int8", "topk"):
        tr = _run(tmp_path, steps=20, grad_compression=kind)
        tr.train()
        losses = tr.losses()
        assert np.isfinite(losses).all()
        assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_microbatch_accumulation_matches_full_batch(tmp_path):
    """nmb=4 gradient accumulation == single big batch (same data/seed)."""
    tr1 = _run(tmp_path, steps=3, microbatch=0)
    tr1.train()
    tr2 = _run(tmp_path, steps=3, microbatch=4)
    tr2.train()
    for x, y in zip(tr1.state.adapter["cores"], tr2.state.adapter["cores"]):
        np.testing.assert_allclose(x, y, atol=2e-4)


def test_straggler_watchdog_fires():
    from repro.distributed import Watchdog
    events = []
    wd = Watchdog(threshold=2.0, min_steps=3,
                  on_straggler=lambda s, dt, ew: events.append(s))
    for i in range(10):
        wd.step(i, 0.1)
    assert not events
    wd.step(10, 1.0)   # 10x the EWMA -> flagged
    assert events == [10]


def test_full_ft_baseline_step():
    """Paper Table 1 "FT" row: full fine-tuning machinery works."""
    from repro.optim import adamw
    from repro.train import train_step as ts
    cfg = registry.get_smoke_config("roberta-base")
    key = jax.random.PRNGKey(0)
    from repro.models import transformer
    base = transformer.init_base_params(cfg, key)
    step = ts.make_full_ft_step(cfg, OptimizerConfig(lr=1e-3),
                                TrainConfig(remat="none"), 10)
    opt = adamw.init_state(base)
    batch = {"tokens": jax.random.randint(key, (4, 16), 0, cfg.vocab_size)}
    base_before = jax.tree_util.tree_map(jnp.copy, base)
    base2, opt2, m = step(base, opt, batch)
    assert np.isfinite(float(m["loss"]))
    # base weights actually moved (unlike the PEFT path, which freezes them)
    moved = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(base2),
        jax.tree_util.tree_leaves(base_before)))
    assert moved > 0
