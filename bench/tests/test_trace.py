"""The trace reduction, on hand-made operations and on a small trace
recorded on a TPU v5e (bench/tests/data/small.xplane.pb: three steps of a
bf16 matmul and a flash attention kernel under the harness's spans)."""
import os

import pytest

from bench.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def _ops(*iv, name="%op.1 = f32[] add()"):
    return [T.Op(name, s, e) for s, e in iv]


def test_union_clip_and_gaps_by_hand():
    dev = {"/device:TPU:0": _ops((0, 10), (5, 20), (30, 40), (95, 120))}
    spans = [("train_step", 0, 50), ("make_batch", 20, 30),
             ("train_step", 60, 110)]
    tr = T.Trace({k: [o for o in v if o.end > 10 and o.start < 100]
                  for k, v in dev.items()}, spans, (10, 100), dev)
    # busy inside [10, 100]: 10-20, 30-40, 95-100
    assert tr.busy_s() == pytest.approx(25e-9)
    assert tr.window_s == pytest.approx(90e-9)
    assert tr.time_of(lambda o: True) == pytest.approx(25e-9)
    assert tr.time_of(lambda o: True, whole=True) == pytest.approx(
        (10 + 15 + 10 + 25) * 1e-9)
    gaps = tr.idle_gaps()
    assert gaps[0] == ["train_step", pytest.approx(55e-9)]   # 40-95
    assert gaps[1] == ["make_batch", pytest.approx(10e-9)]   # 20-30
    assert tr.top_ops(1) == [["op", pytest.approx(25e-9)]]


def test_two_devices_average():
    dev = {"/device:TPU:0": _ops((0, 50)), "/device:TPU:1": _ops((0, 10))}
    tr = T.Trace(dev, [], (0, 100), dev)
    assert tr.busy_s() == pytest.approx(30e-9)


def test_leaves_drop_enclosing_ops():
    ops = [T.Op("%while.1 = () while()", 0, 100),
           T.Op("%a.1 = f32[] add()", 10, 20),
           T.Op("%tt_linear.3 = bf16[8] custom-call(%a.1)", 30, 90),
           T.Op("%b.2 = f32[] add(%tt_linear.3)", 100, 110)]
    got = T.leaves(ops)
    assert [o.instr for o in got] == ["a.1", "tt_linear.3", "b.2"]
    assert [o.is_kernel for o in got] == [False, True, False]


def test_recorded_tpu_trace():
    tr = T.Trace.from_file(DATA, ("train_step", "make_batch"))
    assert 0 < tr.busy_s() < tr.window_s
    assert tr.top_ops(10)
    flash = tr.time_of(lambda o: o.is_kernel and "flash_attention" in o.instr)
    assert 0 < flash < tr.busy_s()
    names = {g[0] for g in tr.idle_gaps(10)}
    assert names <= {"train_step", "make_batch", "none"}
    assert "make_batch" in names
    assert "PLANE /device:TPU:0" in T.describe(DATA)
