"""Benchmark driver — one suite per paper table/figure + roofline summary.

Prints ``name,us_per_call,derived`` CSV rows. Roofline terms come from the
dry-run artifacts (benchmarks/roofline.py); run
``python -m repro.launch.dryrun --all`` first to refresh them.

``--smoke`` runs the CI subset: the kernel-dispatch benches and the serving
smoke benches — fused-vs-unfused parity from the same dispatch seam the
model uses, the paged-vs-dense engine comparison, and the fp-vs-int8
quantized serving comparison (token parity, prefix-cache hit rate and
peak-KV-memory assertions from the engine's own stats) — cheap enough to
gate every CI run against kernel regressions and benchmark bit-rot.

``--json`` additionally writes ``BENCH_kernels.json``, ``BENCH_serving.json``
and ``BENCH_train.json`` at the repo root — the same rows as the CSV (parsed
into objects) plus, for serving, the engines' own stats objects — so
future PRs can diff the perf trajectory machine-readably instead of
scraping stdout.
"""
from __future__ import annotations

import argparse
import json
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _row_dicts(rows: list) -> list:
    """"name,us,derived" CSV strings -> dicts (derived may hold commas)."""
    out = []
    for r in rows:
        name, us, derived = r.split(",", 2)
        out.append({"name": name, "us_per_call": float(us),
                    "derived": derived})
    return out


def _write_json(path: str, payload: dict) -> None:
    full = os.path.join(REPO_ROOT, path)
    with open(full, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {full}", flush=True)


def _emit_json(kernel_rows: list, serving_rows: list,
               train_rows: list) -> None:
    from benchmarks import bench_serving, bench_train
    _write_json("BENCH_kernels.json", {"rows": _row_dicts(kernel_rows)})
    # merge (replace same-name rows / same-label stats, keep the rest)
    # rather than overwrite, so rows written by other jobs — e.g. the
    # sharded-parity job's serving/tp4_vs_tp1 (`bench_serving --mesh`) —
    # survive this writer regardless of execution order
    bench_serving._merge_rows_into_json(serving_rows)
    bench_train._merge_rows_into_json(train_rows)


def main(*, smoke: bool = False, emit_json: bool = False) -> None:
    print("name,us_per_call,derived")
    from benchmarks import (bench_fig2_dmrg, bench_init_ablation,
                            bench_kernels, bench_serving, bench_table1,
                            bench_table2, bench_train, roofline)
    if smoke:
        kernel_rows = bench_kernels.run(smoke=True)
        serving_rows = bench_serving.run(smoke=True)
        train_rows = bench_train.run(smoke=True)
        if emit_json:
            _emit_json(kernel_rows, serving_rows, train_rows)
        return
    bench_table1.run()
    bench_table2.run()
    bench_fig2_dmrg.run()
    bench_init_ablation.run()
    serving_rows = bench_serving.run()
    kernel_rows = bench_kernels.run()
    train_rows = bench_train.run()
    if emit_json:
        _emit_json(kernel_rows, serving_rows, train_rows)
    # roofline summary rows (from dry-run artifacts, if present)
    for out_dir, label in (("artifacts/dryrun", "baseline"),
                           ("artifacts/dryrun_opt", "optimized")):
        if not os.path.isdir(out_dir):
            continue
        rows = roofline.load(out_dir)
        for r in rows:
            if r.get("status") != "OK" or r.get("mesh") != "single":
                continue
            ro = r["roofline"]
            print(f"roofline-{label}/{r['arch']}/{r['shape']},0.0,"
                  f"bound={ro['bound']} compute_s={ro['compute_s']:.3e} "
                  f"memory_s={ro['memory_s']:.3e} "
                  f"collective_s={ro['collective_s']:.3e} "
                  f"fraction={ro['roofline_fraction']:.4f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: kernel-dispatch + serving smoke "
                         "benches (incl. paged-vs-dense and fp-vs-int8 "
                         "engine parity)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_kernels.json / BENCH_serving.json / "
                         "BENCH_train.json at the repo root (rows + "
                         "engine stats)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    main(smoke=args.smoke, emit_json=args.json)
