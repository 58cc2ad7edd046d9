"""The architecture is a unit the harness loads by name.

Pins: the dense decoder's weights, reference and work counts, read through
``bench.lib.cells.architecture``, equal what the harness gave before the
architecture became a package of its own (values computed on that tree at
the seed below: hashes of every leaf at the tiny sizes of each cell, and
the work dicts of both configurations at their published sizes with the
traffic files' shapes). A second architecture added with new files only
runs a cell correctly, and no file of the harness had to change for it."""
import filecmp
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from bench.lib import cells, traffic
from bench.tests import tiny
from bench.tests.tiny import ROOT

SEED = 2 ** 31 + 21

PARAMS = {
    "stablelm-serve-decode": {
        "['adapter']['cores'][0]": "f0ec939751ecf3e4",
        "['adapter']['cores'][1]": "b10ddb8bfceb17e2",
        "['adapter']['cores'][2]": "5309e39b9599a28a",
        "['adapter']['cores'][3]": "2bc1b754f8b0f728",
        "['adapter']['cores'][4]": "7c7da0148449e4dc",
        "['base']['blocks'][0]['ffn']['wd']": "229442a96ef4beb1",
        "['base']['blocks'][0]['ffn']['wg']": "b48c63e562cfcb11",
        "['base']['blocks'][0]['ffn']['wu']": "c74ac29137fafdba",
        "['base']['blocks'][0]['mixer']['wk']": "ee21b8a52158ab6d",
        "['base']['blocks'][0]['mixer']['wo']": "054285698b740b78",
        "['base']['blocks'][0]['mixer']['wq']": "a75cc13b07178f49",
        "['base']['blocks'][0]['mixer']['wv']": "39ea3019d8b45f7c",
        "['base']['blocks'][0]['norm1']['b']": "5b41c19911998ea3",
        "['base']['blocks'][0]['norm1']['w']": "eae42367fcc43b92",
        "['base']['blocks'][0]['norm2']['b']": "5b41c19911998ea3",
        "['base']['blocks'][0]['norm2']['w']": "eae42367fcc43b92",
        "['base']['embed']['tok']": "acedf535af9214fb",
        "['base']['final_norm']['b']": "e25e809fc3c322e6",
        "['base']['final_norm']['w']": "811e6f3d4f8e2fca",
    },
    "stablelm-train-8k": {
        "['adapter']['cores'][0]": "c587ab39a29a9477",
        "['adapter']['cores'][1]": "c07ea8804fff2d4f",
        "['adapter']['cores'][2]": "947faf47ad42c518",
        "['adapter']['cores'][3]": "049ba4c28b86f34b",
        "['base']['blocks'][0]['ffn']['wd']": "229442a96ef4beb1",
        "['base']['blocks'][0]['ffn']['wg']": "b48c63e562cfcb11",
        "['base']['blocks'][0]['ffn']['wu']": "c74ac29137fafdba",
        "['base']['blocks'][0]['mixer']['wk']": "ee21b8a52158ab6d",
        "['base']['blocks'][0]['mixer']['wo']": "054285698b740b78",
        "['base']['blocks'][0]['mixer']['wq']": "a75cc13b07178f49",
        "['base']['blocks'][0]['mixer']['wv']": "39ea3019d8b45f7c",
        "['base']['blocks'][0]['norm1']['b']": "5b41c19911998ea3",
        "['base']['blocks'][0]['norm1']['w']": "eae42367fcc43b92",
        "['base']['blocks'][0]['norm2']['b']": "5b41c19911998ea3",
        "['base']['blocks'][0]['norm2']['w']": "eae42367fcc43b92",
        "['base']['embed']['tok']": "acedf535af9214fb",
        "['base']['final_norm']['b']": "e25e809fc3c322e6",
        "['base']['final_norm']['w']": "811e6f3d4f8e2fca",
    },
}

REFERENCE = {
    "stablelm-serve-decode": {
        "logits_at": "3bce1d65f59b76e4",
    },
    "stablelm-train-8k": {
        "grads": [
            "e44f792caa393a6c",
            "a1b15932a7d3553f",
            "3fcac2ddf9c861fc",
            "2d4f86b35a9a9c38",
        ],
        "logits_at": "3a7b87ced88fb141",
        "loss": "0ecc0e3fb059c34a",
    },
}

WORK = {
    "mistral-large-123b": {
        "serve_forward": {
            "model_flops": 802923368349696.0,
            "paged_bytes": 189154664448.0,
            "paged_flops": 2634605494272.0,
        },
        "train_step": {
            "flash_bytes": 1977090048,
            "flash_flops": 3711757713408.0,
            "model_flops": 78458281525248.0,
            "tokens": 4096,
            "tt_linear_bytes": 26392854528.0,
            "tt_linear_flops": 68110396686336.0,
        },
    },
    "stablelm-1.6b": {
        "serve_forward": {
            "model_flops": 250345364258816.0,
            "paged_bytes": 2829008240640.0,
            "paged_flops": 3512807325696.0,
        },
        "train_step": {
            "flash_bytes": 19478347776,
            "flash_flops": 39587250438144.0,
            "model_flops": 134024991342592.0,
            "tokens": 16384,
            "tt_linear_bytes": 13696499712.0,
            "tt_linear_flops": 13297218748416.0,
        },
    },
}


def _hash(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()[:16]


def _json(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


@pytest.mark.parametrize("cell", list(PARAMS))
def test_params_pinned(tiny_root, cell):
    import jax
    c = cells.load(cell, tiny_root)
    p = cells.architecture(c).seam.params(c.config, c.traffic["adapter"],
                                          SEED)
    flat = jax.tree_util.tree_flatten_with_path(p)[0]
    assert {jax.tree_util.keystr(k): _hash(v) for k, v in flat} == \
        PARAMS[cell]


@pytest.mark.parametrize("cell", list(REFERENCE))
def test_reference_pinned(tiny_root, cell):
    import jax.numpy as jnp
    from bench.kinds import train
    c = cells.load(cell, tiny_root)
    cfg, ad = c.config, c.traffic["adapter"]
    ref = cells.architecture(c).Reference(cfg, ad, SEED)
    cores = ref.cores()
    toks = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 16)).astype(np.int32)
    task = (jnp.asarray([1, 5], jnp.int32) if ad["variant"] == "4+1d"
            else None)
    got = {"logits_at": _hash(ref.logits_at(ref.hidden(toks, task, cores),
                                            [0, 0, 1], [3, 15, 7]))}
    if c.traffic["kind"] == "train":
        b = train.feed_for(cfg, c.traffic, SEED).batch_at(0)
        loss, grads = ref.loss_and_grads(b["tokens"], b["mask"], cores)
        got["loss"] = _hash(jnp.asarray(loss, jnp.float32))
        got["grads"] = [_hash(g) for g in grads]
    assert got == REFERENCE[cell]


@pytest.mark.parametrize("config,train", [
    ("stablelm-1.6b", "train-8k"), ("mistral-large-123b", "train-4k")])
def test_work_pinned(config, train):
    cfg = cells.run_config(_json(os.path.join(
        ROOT, "bench", "configs", config + ".json")))
    work = cells._package(ROOT, cfg["architecture"]).work
    t = _json(os.path.join(ROOT, "bench", "traffic", train + ".json"))
    serve = _json(os.path.join(ROOT, "bench", "traffic", "serve-decode.json"))
    reqs = traffic.serve_requests(serve, cfg["vocab_size"], SEED,
                                  serve["requests"], stream=0)
    rows = [(len(r.prompt), r.max_new, len(r.prompt)) for r in reqs] + [
        (100, 0, 0), (40, 3, 17)]
    d = t["data"]
    assert work.train_step(cfg, t["adapter"], d["batch"], d["seq_len"],
                           {}) == WORK[config]["train_step"]
    assert work.serve_forward(cfg, serve["adapter"], rows, 8, {}) == \
        WORK[config]["serve_forward"]


def test_new_architecture_with_files_only(tmp_path, cpu_peaks):
    """A copy of the benchmark gains a second architecture, a configuration
    that names it, its traffic, limits and tiny sizes, and a cell, all as
    new files and entries; the cell runs correct on the CPU, and every file
    of the harness is in the copy as it is here."""
    import contextlib
    import io
    import jax
    from bench import run
    src = str(tmp_path / "full")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(src, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), src)
    b = os.path.join(src, "bench")
    shutil.copytree(os.path.join(b, "models", "dense_decoder"),
                    os.path.join(b, "models", "dense_decoder_copy"))
    cfg = _json(os.path.join(b, "configs", "stablelm-1.6b.json"))
    cfg.update(name="copy-model", architecture="dense_decoder_copy")
    _dump(cfg, os.path.join(b, "configs", "copy-model.json"))
    shutil.copy(os.path.join(b, "traffic", "train-8k.json"),
                os.path.join(b, "traffic", "copy-train.json"))
    shutil.copy(os.path.join(b, "limits", "stablelm-train-8k.json"),
                os.path.join(b, "limits", "copy-cell.json"))
    shutil.copy(os.path.join(b, "tests", "tiny", "stablelm-train-8k.json"),
                os.path.join(b, "tests", "tiny", "copy-cell.json"))
    spec = _json(os.path.join(src, "BENCHMARK.json"))
    spec["configs"].append({"name": "copy-model", "source": "x",
                            "file": "bench/configs/copy-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "copy-cell", "config": "copy-model",
                              "traffic": "copy-train", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "stablelm-train-8k" in m.get("workloads", []):
            m["workloads"].append("copy-cell")
    _dump(spec, os.path.join(src, "BENCHMARK.json"))

    root = tiny.make(str(tmp_path / "tiny"), src)

    def checks(cell):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            assert run.main(["--workload", cell, "--seed", str(2 ** 31 + 7),
                             "--seconds", "1", "--trace", "0"],
                            devices=jax.devices(), root=root) == 0
        line = json.loads(out.getvalue().strip().splitlines()[-1])
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        return line["correct"], line["checks"]

    # the copy computes what the original does, and so comes out correct
    got = checks("copy-cell")
    assert got[0], got[1]
    assert got == checks("stablelm-train-8k")
    arch = cells.architecture(cells.load("copy-cell", root))
    assert arch.__path__ == [os.path.join(root, "bench", "models",
                                          "dense_decoder_copy")]

    for sub in ("lib", "kinds", "tests", os.path.join("models",
                                                      "dense_decoder")):
        for dirpath, dirs, files in os.walk(os.path.join(ROOT, "bench",
                                                         sub)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                here = os.path.join(dirpath, f)
                there = os.path.join(root, os.path.relpath(here, ROOT))
                assert filecmp.cmp(here, there, shallow=False), there
