"""A copy of the benchmark with tiny cells, for runs on the CPU.

``make(dst, src)`` copies ``bench/`` of ``src`` into ``dst`` and writes into
the copy a ``BENCHMARK.json`` whose cells are ``src``'s cells cut to a tiny
size: every key of each configuration, traffic and limits file as in the
real cell, with the sizes of ``bench/tests/tiny/<cell>.json`` in their
place (``{"config": {...}, "traffic": {...}}``). The real cells' limits are
kept. Every cell has such a file; a new cell gets its CPU runs by adding
one."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make(dst: str, src: str = ROOT) -> str:
    shutil.copytree(os.path.join(src, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = _load(os.path.join(src, "BENCHMARK.json"))
    confs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        size = _load(os.path.join(src, "bench", "tests", "tiny",
                                  w["name"] + ".json"))
        conf = confs[w["config"]]
        cfg = _load(os.path.join(src, conf["file"]))
        cfg.update(size["config"])
        _dump(cfg, os.path.join(dst, conf["file"]))
        tpath = os.path.join(dst, "bench", "traffic", w["traffic"] + ".json")
        tr = _load(tpath)
        tr.update(size["traffic"])
        _dump(tr, tpath)
    _dump(spec, os.path.join(dst, "BENCHMARK.json"))
    return dst
