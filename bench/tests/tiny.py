"""A copy of the benchmark with tiny cells, for runs on the CPU.

``make(dst)`` copies ``bench/`` into ``dst`` and writes into the copy a
``BENCHMARK.json`` whose cells are the real ones cut to a tiny size (every
key of each configuration, traffic and limits file as in the real cell,
with the sizes below in their place). The real cells' limits are kept."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  vocab_size=256)
TINY = {  # cell -> (config sizes, traffic sizes)
    "stablelm-serve-decode": (
        dict(num_attention_heads=4, num_key_value_heads=4),
        dict(engine={"runtime": "live", "max_batch": 4, "cache_len": 64,
                     "out_cap": 32, "num_blocks": 64},
             prompt={"dist": "lognormal", "median": 12, "sigma": 0.5,
                     "min": 8, "max": 24},
             output={"dist": "lognormal", "median": 8, "sigma": 0.5,
                     "min": 4, "max": 16},
             requests=24, warmup_seconds=0.5,
             check={"tokens": 60, "max_requests": 8})),
    "stablelm-train-8k": (
        dict(num_attention_heads=4, num_key_value_heads=4),
        dict(data={"seq_len": 32, "batch": 2, "branching": 4})),
}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(dst: str) -> str:
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = _load(os.path.join(ROOT, "BENCHMARK.json"))
    confs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        if w["name"] not in TINY:
            continue
        msize, tsize = TINY[w["name"]]
        conf = confs[w["config"]]
        cfg = _load(os.path.join(ROOT, conf["file"]))
        cfg.update(TINY_MODEL, **msize)
        _dump(cfg, os.path.join(dst, conf["file"]))
        tpath = os.path.join(dst, "bench", "traffic", w["traffic"] + ".json")
        tr = _load(tpath)
        tr.update(tsize)
        _dump(tr, tpath)
    _dump(spec, os.path.join(dst, "BENCHMARK.json"))
    return dst
