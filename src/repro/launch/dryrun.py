import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). 512 fake host devices back the production meshes:
# single-pod (data=16, model=16) and multi-pod (pod=2, data=16, model=16).
"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell and extract the roofline terms from the compiled artifact.

Per cell it records to artifacts/dryrun/<arch>__<shape>__<mesh>.json:
  * memory_analysis (args/temp/output bytes per device — proves it fits),
  * cost_analysis flops + bytes accessed (per-device SPMD program),
  * per-collective wire bytes parsed from the optimized HLO,
  * the three roofline terms (v5e: 197 TFLOP/s bf16, 819 GB/s HBM,
    ~50 GB/s/link ICI) + MODEL_FLOPS and the useful-compute ratio.

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--force]
"""
import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

# ---------------------------------------------------------------------------
# roofline constants (TPU v5e per chip)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-chip wire-byte estimate per collective kind from optimized HLO.

    Shapes in the post-SPMD module are per-chip. Ring estimates:
      all-gather: out x (g-1)/g      all-reduce: 2 x out x (g-1)/g
      reduce-scatter: out x (g-1)    all-to-all: out x (g-1)/g
      collective-permute: out
    ``sum_output_bytes`` is the raw operand/result-size sum (the assignment's
    bookkeeping convention); ``wire_bytes`` is what the roofline term uses.
    """
    out = {k: {"count": 0, "output_bytes": 0, "wire_bytes": 0.0}
           for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+)$", stripped)
        if m is None:
            continue
        rhs = m.group(1)
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start)?\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        # result type = everything before the op name
        type_str = rhs.split(kind)[0]
        nbytes = _shape_bytes(type_str)
        g = 1
        gm = _GROUP_IOTA_RE.search(rhs)
        if gm:
            g = int(gm.group(2))
        else:
            gm = _GROUP_LIST_RE.search(rhs)
            if gm:
                g = len(gm.group(1).split(","))
        if g <= 1:
            g_eff = 2  # degenerate parse; assume pairwise
        else:
            g_eff = g
        frac = (g_eff - 1) / g_eff
        if kind == "all-gather":
            wire = nbytes * frac
        elif kind == "all-reduce":
            wire = 2 * nbytes * frac
        elif kind == "reduce-scatter":
            wire = nbytes * (g_eff - 1)
        elif kind == "all-to-all":
            wire = nbytes * frac
        else:  # collective-permute
            wire = nbytes
        out[kind]["count"] += 1
        out[kind]["output_bytes"] += nbytes
        out[kind]["wire_bytes"] += wire
    out["total_wire_bytes"] = sum(
        v["wire_bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_output_bytes"] = sum(
        v["output_bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def model_flops(cfg, shape, spec) -> dict:
    """MODEL_FLOPS = 6·N_active·D for training, 2·N_active·D for inference
    (D = tokens processed; decode: D = global_batch x 1 token)."""
    from repro.models.model import count_params  # lazy; no jax init issues
    from repro.models import transformer

    base_sds = jax.eval_shape(
        lambda: transformer.init_base_params(cfg, jax.random.PRNGKey(0)))

    def tree_n(tree):
        return int(sum(np.prod(x.shape) for x in
                       jax.tree_util.tree_leaves(tree)))

    n_total = tree_n(base_sds)
    # active params: MoE uses top-k of num_experts experts
    n_active = n_total
    if cfg.num_experts:
        expert_leaves = 0
        for p, leaf in __import__("repro.sharding.rules",
                                  fromlist=["_paths"])._paths(base_sds):
            if p.split("/")[-1] in ("e_wg", "e_wu", "e_wd"):
                expert_leaves += int(np.prod(leaf.shape))
        active_frac = cfg.experts_per_token / cfg.num_experts
        n_active = n_total - expert_leaves + int(expert_leaves * active_frac)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        flops = 2 * n_active * tokens
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        flops = 2 * n_active * tokens
    return {"n_total": n_total, "n_active": n_active, "tokens": tokens,
            "model_flops": flops}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "artifacts/dryrun", force: bool = False,
             run_kwargs: dict | None = None, tag: str = "") -> dict:
    from repro import configs as config_registry
    from repro.launch import mesh as mesh_lib
    from repro.launch import specs as specs_lib
    from repro.sharding import rules

    mesh_name = "multi" if multi_pod else "single"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{mesh_name}{tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = config_registry.get_config(arch)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag}
    if not config_registry.supports_shape(cfg, shape_name):
        rec["status"] = "SKIP"
        rec["reason"] = ("long_500k needs sub-quadratic decode; "
                         f"{arch} is full-attention (DESIGN.md §4)")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        return rec

    run = specs_lib.make_run_config(arch, shape_name, **(run_kwargs or {}))
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            rules.set_seq_axis("model" if run.shape.kind != "decode"
                               else None)
            try:
                cell = specs_lib.input_specs(run, mesh)
                lowered = cell["fn"].lower(*cell["args"])
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
            finally:
                rules.set_seq_axis(None)

        mem = compiled.memory_analysis()
        mem_rec = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_rec[k] = int(v)
        # raw XLA cost_analysis kept for reference only: it counts while-loop
        # bodies ONCE (wrong under scan) — see launch/hlo_analysis.py.
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        cost_rec = {k: float(v) for k, v in cost.items()
                    if isinstance(v, (int, float))
                    and k in ("flops", "bytes accessed")}
        hlo = compiled.as_text()
        from repro.launch import hlo_analysis
        hc = hlo_analysis.analyze(hlo)
        coll = {k: v for k, v in hc["coll"].items()}
        coll["total_wire_bytes"] = hc["collective_wire_bytes"]
        coll["total_payload_bytes"] = hc["collective_payload_bytes"]
        # TPU-native estimate: on CPU, bf16 data is often upcast to f32
        # BEFORE collectives (GEMM legalization); a bf16-native TPU moves
        # half those bytes. Conservatively halve only the f32 share.
        import jax.numpy as _jnp
        bf16_model = cfg.compute_dtype == _jnp.bfloat16
        coll["total_wire_bytes_tpu"] = (
            hc["collective_wire_bytes"]
            - (hc["collective_wire_bytes_f32"] / 2 if bf16_model else 0.0))
        mem_rec["cpu_f32_upcast_bytes"] = int(hc["cpu_f32_upcast_bytes"])
        if "temp_size_in_bytes" in mem_rec:
            # CPU legalizes bf16 GEMMs via hoisted f32 weight upcasts that
            # don't exist on TPU — subtract for the TPU estimate
            mem_rec["tpu_temp_estimate_bytes"] = (
                mem_rec["temp_size_in_bytes"]
                - mem_rec["cpu_f32_upcast_bytes"])
        mf = model_flops(cfg, run.shape, cell["spec"])

        chips = int(np.prod(mesh.devices.shape))
        flops_per_chip = hc["flops"]
        bytes_per_chip = hc["bytes"]
        cost_rec["hlo_flops_per_chip"] = flops_per_chip
        cost_rec["hlo_bytes_per_chip"] = bytes_per_chip
        compute_s = flops_per_chip / PEAK_FLOPS
        memory_s = bytes_per_chip / HBM_BW
        collective_s = coll["total_wire_bytes_tpu"] / LINK_BW
        terms = {"compute_s": compute_s, "memory_s": memory_s,
                 "collective_s": collective_s}
        bound = max(terms, key=terms.get)
        hlo_flops_global = flops_per_chip * chips
        rec.update({
            "status": "OK",
            "chips": chips,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory_analysis": mem_rec,
            "cost_analysis": cost_rec,
            "collectives": coll,
            "model_flops": mf,
            "roofline": {
                **{k: float(v) for k, v in terms.items()},
                "bound": bound.replace("_s", ""),
                "useful_compute_ratio": (
                    mf["model_flops"] / hlo_flops_global
                    if hlo_flops_global else None),
                "roofline_fraction": (
                    compute_s / max(terms.values())
                    if max(terms.values()) > 0 else None),
            },
        })
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--adapter", default="metatt")
    ap.add_argument("--rank", type=int, default=16)
    args = ap.parse_args()

    from repro import configs as config_registry
    from repro.config.base import SHAPES

    archs = config_registry.ARCH_IDS if (args.all or not args.arch) \
        else (args.arch,)
    shapes = tuple(SHAPES) if (args.all or not args.shape) else (args.shape,)
    meshes = {"single": (False,), "multi": (True,),
              "both": (False, True)}[args.mesh]

    run_kwargs = {"adapter_kind": args.adapter, "adapter_rank": args.rank}
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.time()
                rec = run_cell(arch, shape, mp, out_dir=args.out,
                               force=args.force, run_kwargs=run_kwargs)
                status = rec.get("status")
                extra = ""
                if status == "OK":
                    r = rec["roofline"]
                    extra = (f"bound={r['bound']} "
                             f"compute={r['compute_s']:.3e}s "
                             f"mem={r['memory_s']:.3e}s "
                             f"coll={r['collective_s']:.3e}s")
                elif status == "FAIL":
                    extra = rec.get("error", "")[:160]
                print(f"[{status}] {arch} x {shape} x "
                      f"{'multi' if mp else 'single'} "
                      f"({time.time()-t0:.0f}s) {extra}", flush=True)


if __name__ == "__main__":
    main()
