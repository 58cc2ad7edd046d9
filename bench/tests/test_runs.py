"""Whole runs of the tiny cells on the CPU, the chip check skipped: sound
runs come out correct; each fault a cell can have, planted in the program
underneath the timed path, and the control in the program's place, come
out not correct under the real cells' limits. Every cell of
``BENCHMARK.json`` runs, at the sizes of its ``bench/tests/tiny`` file."""
import contextlib
import io
import json
import os

import pytest

from bench.tests.tiny import ROOT

#: the kind of cell (its traffic file's ``kind``) each fault can occur in
FAULT_KIND = {"state_unchanged": "train", "half_batch": "train",
              "token_altered": "serve"}


def _kinds() -> dict:
    """Each cell of BENCHMARK.json -> its kind."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    out = {}
    for w in spec["workloads"]:
        with open(os.path.join(ROOT, "bench", "traffic",
                               w["traffic"] + ".json")) as f:
            out[w["name"]] = json.load(f)["kind"]
    return out


KINDS = _kinds()


def _run(root, cell, seed=2 ** 31 + 5, seconds=1.0):
    import jax
    from bench import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"],
                        devices=jax.devices(), root=root) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", list(KINDS))
def test_sound_run_is_correct(tiny_root, cpu_peaks, cell):
    line = _run(tiny_root, cell)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["device"]["platform"] == "cpu"
    for m in line["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for fault, kind in FAULT_KIND.items()
    for cell, k in KINDS.items() if k == kind])
def test_fault_is_not_correct(tiny_root, cpu_peaks, cell, fault):
    from bench.lib import faults
    with faults.FAULTS[fault]():
        line = _run(tiny_root, cell)
    assert not line["correct"], line["checks"]
    if fault == "state_unchanged":
        assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", list(KINDS))
def test_control_is_not_correct(tiny_root, cpu_peaks, cell):
    """The reference in float8, in the program's place, fails a limit."""
    import jax
    from bench import calibrate, run
    from bench.lib import cells
    c = cells.load(cell, tiny_root)
    args = run.parse(["--workload", cell, "--seed", str(2 ** 31 + 11),
                      "--seconds", "1"])
    res = cells.kind(c).run(c, args, 0.0, jax.devices())
    got = calibrate.control(c, args.seed, res["sample"])
    assert any(got[k] > c.limits[k] for k in c.limits), (got, c.limits)
