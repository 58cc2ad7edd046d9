"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 8]

In one process (the set-up is long): for each seed, one run of the cell with
a short window, which prints the numbers that decide ``correct`` (the lower
readings: the program, sound). For each control seed it also puts the
control in the program's place: the cell's reference in float8 (e4m3, the
precision below the bfloat16 that the configurations state), read on the
same prompts and served tokens, or on the same batches, and prints the same
numbers for it (the upper readings). ``--fault`` plants a fault of
``bench/lib/faults.py`` under the program instead. One JSON line per
reading.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def control(cell, seed: int, sample) -> dict:
    from bench.lib import cells, compare
    mod = cells.reference(cell)
    adapter = cell.traffic["adapter"]
    ref = mod.Reference(cell.config, adapter, seed)
    low = mod.Reference(cell.config, adapter, seed, quant="fp8")
    if cell.traffic["kind"] == "serve":
        prompts, served, tasks = sample
        gaps, agree = cells.kind(cell).served_gaps(
            ref, ref.cores(), prompts, served, tasks, top_of=low)
        return {"max_logit_gap": float(gaps.max()), "agree": agree}
    batches, want = sample
    steps = cell.traffic["check"]["steps"]
    got = compare.reference_steps(low, batches,
                                  cell.traffic["train"]["optimizer"], steps)
    return {**compare.train_checks(got, want),
            **compare.details(got, want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--fault", default="",
                    help="plant this fault (bench/lib/faults.py) under "
                    "the program for every seed")
    a = ap.parse_args(argv)
    from bench import run as run_mod
    from bench.lib import cells, faults
    run_mod.compile_cache_on()
    cell = cells.load(a.workload, ROOT)
    devs = run_mod.require_chips(cell.chips)
    kind = cells.kind(cell)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    ctrl = [int(s) for s in a.control_seeds.split(",") if s]
    for seed in dict.fromkeys(seeds + ctrl):
        args = run_mod.parse(["--workload", a.workload, "--seed", str(seed),
                              "--seconds", str(a.seconds)])
        t0 = time.perf_counter()
        with (faults.FAULTS[a.fault]() if a.fault
              else contextlib.nullcontext()):
            res = kind.run(cell, args, t0, devs)
        line = {"seed": seed, "who": a.fault or "program",
                **{k: c["value"] for k, c in res["checks"].items()},
                **res.get("details", {})}
        print(json.dumps(line), flush=True)
        if seed in ctrl:
            got = control(cell, seed, res["sample"])
            print(json.dumps({"seed": seed, "who": "control", **got}),
                  flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
