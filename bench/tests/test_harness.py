"""The harness is driven by data, seeded, and refuses to run off the chip."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.lib import cells, traffic
from bench.lib.weights import seed_key
from bench.models.dense_decoder import weights
from bench.tests.tiny import ROOT


def test_new_config_traffic_metric_found_by_name(tmp_path):
    from bench.tests import tiny
    root = tiny.make(str(tmp_path))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(root, "bench/configs/stablelm-1.6b.json")))
    cfg["name"] = "new-model"
    json.dump(cfg, open(os.path.join(root, "bench/configs/new-model.json"), "w"))
    tr = json.load(open(os.path.join(root, "bench/traffic/serve-decode.json")))
    tr["requests"] = 7
    json.dump(tr, open(os.path.join(root, "bench/traffic/new-mix.json"), "w"))
    json.dump({"max_logit_gap": 0.5},
              open(os.path.join(root, "bench/limits/new-cell.json"), "w"))
    with open(os.path.join(root, "bench/metrics/new_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    spec["configs"].append({"name": "new-model", "source": "x",
                            "file": "bench/configs/new-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new-cell", "config": "new-model",
                              "traffic": "new-mix", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "serve_tokens_per_s",
                              "workloads": ["new-cell"]})
    spec["end_to_end"][0]["workloads"].append("new-cell")
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    cell = cells.load("new-cell", root)
    assert cell.config["name"] == "new-model"
    assert cell.traffic["requests"] == 7
    assert cell.limits == {"max_logit_gap": 0.5}
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert [m["name"] for m in cell.end_to_end] == ["serve_tokens_per_s",
                                                   "setup_s"]
    assert cells.metric_reader(root, "new_metric")({}) == 42.0
    assert cells.kind(cell).__name__.endswith("serve")


def test_every_real_cell_resolves():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = cells.load(w["name"], ROOT)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cells.metric_reader(ROOT, m["name"]))
        assert cells.architecture(cell).Reference


def test_same_seed_same_requests():
    tr = json.load(open(os.path.join(ROOT, "bench/traffic/serve-decode.json")))
    a = traffic.serve_requests(tr, 1000, 2 ** 31 + 77, 96)
    b = traffic.serve_requests(tr, 1000, 2 ** 31 + 77, 96)
    c = traffic.serve_requests(tr, 1000, 5, 96)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.task == y.task for x, y in zip(a, b))
    # another seed: the same lengths in the same order (the same work),
    # the same tasks in another order, other tokens
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in c]
    assert [r.max_new for r in a] == [r.max_new for r in c]
    assert sorted(r.task for r in a) == sorted(r.task for r in c)
    assert [r.task for r in a] != [r.task for r in c]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # each block of the list holds the same multiset of lengths
    blk = tr["block"]
    assert sorted(len(r.prompt) for r in a[blk:2 * blk]) == \
        sorted(len(r.prompt) for r in a[2 * blk:3 * blk])
    # the first block is a batch already under way: a few prompt tokens
    # left to prefill, and what is left of each output, from a few tokens
    # up, never more than a whole output
    left = tr["first_block"]["prompt_left"]
    assert all(len(r.prompt) == left for r in a[:blk])
    assert all(len(r.prompt) >= tr["prompt"]["min"] for r in a[blk:])
    first = [r.max_new for r in a[:blk]]
    assert min(first) < tr["output"]["min"] <= min(r.max_new
                                                    for r in a[blk:])
    assert max(first) <= tr["output"]["max"]


def test_configs_hold_the_source_and_state_what_runs():
    """A configuration file holds the source's keys; what the program runs
    otherwise is under ``runs_as``, and without it neither the program's
    seam nor the reference takes the file."""
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in spec["configs"]:
        raw = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) <= set(raw) and not (
            set(c["reduced"]) & set(raw["runs_as"]))
        run = cells.run_config(raw)
        cells._package(ROOT, raw["architecture"]).seam.model_config(run)
        changed = {k for k in raw["runs_as"] if raw[k] != run[k]}
        assert changed == set(raw["runs_as"])
    raw = json.load(open(os.path.join(
        ROOT, "bench/configs/stablelm-1.6b.json")))
    assert raw["partial_rotary_factor"] == 0.25 and raw["use_qkv_bias"]
    arch = cells._package(ROOT, raw["architecture"])
    tr = json.load(open(os.path.join(ROOT, "bench/traffic/train-8k.json")))
    for bad in (raw, dict(cells.run_config(raw), use_qkv_bias=True)):
        with pytest.raises(ValueError):
            arch.seam.model_config(bad)
        with pytest.raises(ValueError):
            arch.Reference(bad, tr["adapter"], 1)


def test_same_seed_same_batches():
    f1 = traffic.MarkovFeed(500, 64, 2, seed=2 ** 33 + 1)
    f2 = traffic.MarkovFeed(500, 64, 2, seed=2 ** 33 + 1)
    b1, b2 = [next(f1) for _ in range(3)], [next(f2) for _ in range(3)]
    for x, y in zip(b1, b2):
        assert np.array_equal(x["tokens"], y["tokens"])
    rows = np.concatenate([b["tokens"] for b in b1])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert not np.array_equal(
        traffic.MarkovFeed(500, 64, 2, seed=3).batch_at(0)["tokens"],
        b1[0]["tokens"])


def test_zipf_and_quantiles():
    ids = traffic.zipf_counts(16, 1.0, 1000)
    assert len(ids) == 1000 and np.bincount(ids)[0] > np.bincount(ids)[15]
    ln = traffic.quantile_lengths({"dist": "lognormal", "median": 128,
                                   "sigma": 0.6, "min": 64, "max": 512}, 101)
    assert ln.min() >= 64 and ln.max() <= 512 and ln[50] == 128


def test_one_layer_matches_the_stack():
    import jax
    cfg = dict(json.load(open(os.path.join(
        ROOT, "bench/configs/mistral-large-123b.json"))),
        hidden_size=32, intermediate_size=48, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, vocab_size=64,
        num_hidden_layers=3)
    tr = json.load(open(os.path.join(ROOT, "bench/traffic/train-4k.json")))
    key = seed_key(2 ** 31 + 9)
    st = jax.jit(lambda k: weights.stacked(cfg, tr["adapter"], k))(key)
    one = weights.layer(cfg, key, 2)
    for k, v in one.items():
        assert np.array_equal(np.asarray(st["layers"][k][2]), np.asarray(v))


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-serve-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "{" not in p.stdout
    assert "TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ cannot run."""
    import shutil
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-serve-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
