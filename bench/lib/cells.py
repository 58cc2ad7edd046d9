"""Finds a cell's pieces by the names in ``BENCHMARK.json``: its
configuration file, its traffic mix (``bench/traffic/<traffic>.json``), its
limits (``bench/limits/<cell>.json``), its kind of run
(``bench/kinds/<kind>.py``, named by the traffic file), its architecture
(the package ``bench/models/<architecture>/``, named by the configuration
file: weights, reference, work counts and the program's seam) and the
readers of its per-layer metrics (``bench/metrics/<metric>.py``). Adding a
cell, a mix, a metric or an architecture adds files and entries; no file
here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: str


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def run_config(conf: dict) -> dict:
    """The configuration as it runs: the file's keys, which are the
    source's (cuts of depth excepted, as ``reduced`` lists them), with its
    ``runs_as`` over them, the architecture departures that the program
    cannot avoid (the file's ``departures`` say why)."""
    return {**conf, **conf.get("runs_as", {})}


def load(workload: str, root: str) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = os.path.join(root, "bench")
    config = run_config(_json(os.path.join(root, conf["file"])))
    traffic = _json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    limits = _json(os.path.join(bench, "limits", workload + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                per_layer, root)


def _module(root: str, sub: str, name: str):
    path = os.path.join(root, "bench", sub, name + ".py")
    key = f"bench_{sub}_" + "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind(cell: Cell):
    return _module(cell.root, "kinds", cell.traffic["kind"])


def _package(root: str, name: str):
    """The package ``bench/models/<name>/`` under ``root``, loaded as a
    package of its own (its files import one another relatively), once per
    directory."""
    path = os.path.join(root, "bench", "models", name)
    key = "bench_models_" + "".join(
        c if c.isalnum() else "_" for c in os.path.abspath(path))
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, os.path.join(path, "__init__.py"),
            submodule_search_locations=[path])
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


def architecture(cell: Cell):
    """The cell's architecture: the package that its configuration file's
    ``architecture`` key names, with ``Reference``, ``weights``, ``work``
    and ``seam``."""
    return _package(cell.root, cell.config["architecture"])


def metric_reader(root: str, name: str):
    return _module(root, "metrics", name).read
