"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell's configuration, traffic mix, limits
and metric readers are found by the names in ``BENCHMARK.json``. The run
makes its weights and inputs from ``--seed``, warms up the cell's own
shapes, measures for ``--seconds`` and checks what the timed path produced
against the plain reference. ``--trace 1`` reports the per-layer metrics
instead of the end-to-end ones, from a profiler trace of a further short
window. Without a TPU (or with fewer chips than the cell asks for) it exits
nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(chips: int):
    """The devices, or exit nonzero: no TPU, or fewer chips than asked."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {devs[0].platform!r} "
                 "devices. There is no fallback.")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips; JAX found "
                 f"{len(devs)}")
    return devs


def compile_cache_on() -> None:
    """The program's persistent compile cache, for every program, small
    ones too, so that only a checkout's first run compiles."""
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None, *, devices=None, root=ROOT, t_start=None) -> int:
    """``devices`` stands in for the chip check (tests only)."""
    args = parse(argv)
    from bench.lib import cells, report
    cell = cells.load(args.workload, root)
    compile_cache_on()
    if devices is None:
        devs = require_chips(cell.chips)
        report.log(f"chip found {time.perf_counter() - T_START:.2f}s "
                   "after start")
    else:
        devs = devices
    res = cells.kind(cell).run(cell, args, T_START if t_start is None
                               else t_start, devs)
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = cells.metric_reader(root, m["name"])(res["ctx"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(res["e2e"][m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    report.emit(correct=res["correct"], attempted=res["attempted"],
                failed=res["failed"], metrics=metrics,
                device=res["device"], checks=res["checks"],
                breakdown=res.get("breakdown"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
