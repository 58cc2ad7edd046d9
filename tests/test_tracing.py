"""Host spans (``repro.tracing``) of the paged serving loop and the trainer,
read back from a profiler trace taken on the CPU the way a benchmark reads
one from the chip; named scopes in the compiled train step; the survey of
device time by scope."""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs as registry
from repro import tracing
from repro.config.base import (OptimizerConfig, RegistryConfig, RunConfig,
                               SHAPES, ServeConfig, TrainConfig)
from repro.core import tt as ttlib
from repro.core.dmrg import RankSchedule
from repro.data import LMStream
from repro.models import model as M
from repro.serving import AdapterRuntime, Engine, Request
from repro.train.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = registry.get_smoke_config("stablelm-1.6b")
KEY = jax.random.PRNGKey(0)


def _traced_spans(logdir, fn):
    """Run ``fn`` under the profiler; the program's spans it left on the
    host planes as [(name, start_ns, end_ns, args)]."""
    with jax.profiler.trace(str(logdir)):
        fn()
    path = sorted(glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in tracing.SPANS:
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


def _each_inside(spans, child, parent):
    """Every ``child`` span lies inside some ``parent`` span."""
    parents = [s for s in spans if s[0] == parent]
    kids = [s for s in spans if s[0] == child]
    assert kids, child
    return all(any(p[1] <= k[1] and k[2] <= p[2] for p in parents)
               for k in kids)


def test_engine_spans_nest_in_the_paged_loop(tmp_path):
    run = RunConfig(model=CFG, shape=SHAPES["decode_32k"],
                    adapter_kind="metatt", adapter_variant="4+1d",
                    num_tasks=3, adapter_rank=4)
    spec = M.build_adapter_spec(run)
    params = M.init_params(CFG, spec, KEY)
    params["adapter"] = {"cores": ttlib.random_tt(
        KEY, spec.cfg.mode_sizes, 4, scale=0.8)}
    rt = AdapterRuntime.build("live", params["base"], spec,
                              params["adapter"], params["frozen"])
    # one slot serializes the requests: later ones share the first's
    # cached pages, one copy-on-write among them; task 0 faults its slice
    base_p = np.asarray(jax.random.randint(KEY, (10,), 0, CFG.vocab_size))
    div = np.concatenate([base_p[:6], np.array([1, 2, 3], np.int32)])
    reqs = [Request(base_p, 6, task=1), Request(div, 6, task=1),
            Request(base_p, 6, task=1), Request(base_p[:5], 3, task=0)]
    eng = Engine(CFG, rt, serve=ServeConfig(
        max_batch=1, cache_len=32, out_cap=8, page_size=8, prefill_chunk=4,
        registry=RegistryConfig(max_resident_tasks=2)))
    # warm-up on another task and prompt, so the traced call still faults
    # tasks 0 and 1 in and finds only its own prefixes cached
    other = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (10,), 0,
                                          CFG.vocab_size))
    eng.generate([Request(other, 3, task=2)])
    spans = _traced_spans(tmp_path, lambda: eng.generate(reqs))
    names = {s[0] for s in spans}
    want = {n for n in tracing.SPANS if n.startswith("engine.")}
    assert names == want
    for child in ("engine.sweep", "engine.admit", "engine.dispatch",
                  "engine.wait", "engine.harvest"):
        assert _each_inside(spans, child, "engine.iteration"), child
    for child in ("engine.adapter_fault", "engine.cow"):
        assert _each_inside(spans, child, "engine.admit"), child
    admits = [s[3] for s in spans if s[0] == "engine.admit"]
    assert [a["rid"] for a in admits] == [0, 1, 2, 3]
    assert [a["task"] for a in admits] == [1, 1, 1, 0]
    assert {a["slot"] for a in admits} == {0}
    st = eng.last_stats
    assert st.cow_copies >= 1 and st.adapter_faults >= 1
    assert len(st.decode_call_log) == st.decode_calls
    assert sum(e - s for _, _, s, e in st.decode_call_log) \
        == st.decode_steps


def test_trainer_spans_nest_in_each_step(tmp_path):
    run = RunConfig(
        model=CFG, shape=SHAPES["train_4k"], adapter_kind="metatt",
        adapter_rank=4, adapter_alpha=4.0,
        optimizer=OptimizerConfig(lr=2e-2, warmup_ratio=0.1),
        train=TrainConfig(seed=3, ckpt_every=1,
                          ckpt_dir=str(tmp_path / "ckpt"), remat="none"))
    data = LMStream(vocab_size=CFG.vocab_size, seq_len=32, batch=4,
                    seed=11, branching=2)
    tr = Trainer(run=run, data=data, total_steps=2, steps_per_epoch=1,
                 rank_schedule=RankSchedule(milestones=((1, 2),)))
    spans = _traced_spans(tmp_path / "trace", lambda: tr.train(2))
    names = {s[0] for s in spans}
    assert names == {n for n in tracing.SPANS if n.startswith("trainer.")}
    for child in ("trainer.batch", "trainer.dispatch", "trainer.wait",
                  "trainer.dmrg"):
        assert _each_inside(spans, child, "trainer.step"), child
    steps = [s[3]["step_num"] for s in spans if s[0] == "trainer.step"]
    assert steps == [0, 1]


def test_train_step_phases_carry_named_scopes():
    """forward, its gradient and the optimizer are told apart by the op
    names of the compiled step: what the scope survey groups by."""
    run = RunConfig(model=CFG, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_rank=4,
                    train=TrainConfig(remat="none"))
    data = LMStream(vocab_size=CFG.vocab_size, seq_len=16, batch=2,
                    seed=1, branching=2)
    tr = Trainer(run=run, data=data, total_steps=1)
    text = tr.step_fn.lower(tr.state, tr.base, tr.frozen,
                            tr._next_batch(0)).compile().as_text()
    scopes = {tracing.scope_of(n)
              for n in re.findall(r'op_name="([^"]*)"', text)}
    assert {"gradient/jvp(forward)", "gradient/transpose(jvp(forward))",
            "optimizer"} <= scopes


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step_fn)/gradient/transpose(jvp(forward))/while/body/dot_general",
     "gradient/transpose(jvp(forward))"),
    ("jit(step_fn)/optimizer/mul", "optimizer"),
    ("jit(_paged_decode_impl)/while/body/model/dot_general", "model"),
    ("jit(_paged_decode_impl)/while/cond/reduce_or", "(none)"),
    ("jit(<lambda>)/jit(flash_attention)/pallas_call", "(none)"),
    ("", "(none)"),
])
def test_scope_of(op_name, scope):
    assert tracing.scope_of(op_name) == scope


def test_scope_survey_of_a_recorded_tpu_trace():
    """The survey reads every leaf device operation of a trace recorded on
    a TPU v5e: its total is the device time ProfileData reports."""
    path = os.path.join(ROOT, "bench", "tests", "data", "small.xplane.pb")
    times = tracing.scope_times(path)
    durs = [ev.duration_ns * 1e-9
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/device:TPU:0"
            for line in plane.lines if line.name == "XLA Ops"
            for ev in line.events]
    assert sum(durs) > 0
    # ProfileData rounds each duration to whole nanoseconds
    assert abs(sum(times.values()) - sum(durs)) <= 1e-9 * len(durs)


def test_latent_moe_layers_carry_their_named_scopes():
    """Latent attention and the dropless expert layer's router, held
    experts and shared expert each name their ops, in the forward, the
    backward and remat's recompute, where ``named_scope_times`` finds
    them."""
    import dataclasses
    kimi = dataclasses.replace(
        registry.get_smoke_config("kimi-k2-1t-a32b"), experts_held=4,
        expert_offset=2).validate()
    run = RunConfig(model=kimi, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_rank=4,
                    train=TrainConfig(remat="block"))
    data = LMStream(vocab_size=kimi.vocab_size, seq_len=16, batch=2,
                    seed=1, branching=2)
    tr = Trainer(run=run, data=data, total_steps=1)
    text = tr.step_fn.lower(tr.state, tr.base, tr.frozen,
                            tr._next_batch(0)).compile().as_text()
    parts = {p for n in re.findall(r'op_name="([^"]*)"', text)
             for p in n.split("/")}
    assert set(tracing.MODEL_SCOPES) <= parts


def test_named_scope_survey_of_a_recorded_tpu_trace():
    """Every leaf device operation lands under one named scope or
    "(other)": the survey's total is the scope survey's."""
    path = os.path.join(ROOT, "bench", "tests", "data", "small.xplane.pb")
    named = tracing.named_scope_times(path, tracing.MODEL_SCOPES)
    assert list(named)[0] == "(other)" and set(named) == {
        "(other)", *tracing.MODEL_SCOPES}
    assert abs(sum(named.values())
               - sum(tracing.scope_times(path).values())) < 1e-12
