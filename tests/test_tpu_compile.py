"""Compile guard for the TPU: every main-path Pallas kernel, at
stablelm-1.6b widths (Kimi K2's for latent attention and the grouped
expert matmul), compiled natively (``interpret=False``) for a
described — not attached — TPU v5e (``v5e:2x2``).

Interpret mode cannot see what the chip's compiler refuses: block shapes
off the (8, 128) tiling, layouts Mosaic does not lower, more VMEM than a
kernel may use. The TPU compiler is installed with JAX, so these compiles
run without a chip; each test checks that the program really holds the
kernel (``tpu_custom_call``). Nothing runs, so this says nothing about
results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers must all
collect the same tests.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("stablelm-1.6b")
D, F, H = CFG.d_model, CFG.d_ff, CFG.num_heads
KV, HD = CFG.num_kv_heads, CFG.resolved_head_dim
RANK, SLOTS, T, CHUNK, PAGE, CACHE = 8, 8, 2048, 8, 16, 1024
NBLK, PTAB = SLOTS * CACHE // PAGE, CACHE // PAGE
NAT = dict(backend="pallas", interpret=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, *specs):
    return [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]


bf, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
_POOL, _SPOOL = (NBLK, KV, PAGE, HD), (NBLK, KV, 1, PAGE)
#: stablelm-serve-decode's paged shapes: 32 slots over 640 blocks of 16, a
#: table of 65 pages (cache_len 1024 + 16), chunk 8; the verifier's chunk
#: is spec_k + 1 with spec_k 3
SERVE_SLOTS, SERVE_NBLK, SERVE_PTAB, VERIFY_CHUNK = 32, 640, 65, 4
_SERVE_POOL = (SERVE_NBLK, KV, PAGE, HD)
#: Kimi K2's latent attention and expert widths (configs/kimi_k2.py)
MLA_H, MLA_QK, MLA_V, MLA_SCALE = 64, 192, 128, 192 ** -0.5 * 1.8133
KIMI_D, KIMI_FF = 7168, 2048
#: rows of one 4096-token chunk of the dropless layer (8 held experts)
GMM_R = -(-(4096 * 8 + 8 * 127) // 128) * 128
_SERVE_SPOOL = (SERVE_NBLK, KV, 1, PAGE)


def _paged_serve(chunk, pool_dtype):
    """The paged kernel case at the serving cell's shapes."""
    specs = [((SERVE_SLOTS, chunk, H, HD), bf), (_SERVE_POOL, pool_dtype),
             (_SERVE_POOL, pool_dtype), ((SERVE_SLOTS, SERVE_PTAB), i32),
             ((SERVE_SLOTS,), i32)]
    if pool_dtype == i8:
        return (lambda q, k, v, t, p, ks, vs: ops.paged_decode_attention(
            q, k, v, t, p, k_scale=ks, v_scale=vs, **NAT),
            specs + [(_SERVE_SPOOL, f32), (_SERVE_SPOOL, f32)])
    return (lambda q, k, v, t, p: ops.paged_decode_attention(
        q, k, v, t, p, **NAT), specs)


#: name -> (fn, argument (shape, dtype) list); the model's own call shapes
CASES = {
    "tt_linear_train": (
        lambda x, w, a, b: ops.tt_linear(x, w, a, b, **NAT),
        [((4096, D), bf), ((D, F), bf), ((D, RANK), bf), ((RANK, F), bf)]),
    "tt_linear_decode": (
        lambda x, w, a, b: ops.tt_linear(x, w, a, b, **NAT),
        [((SLOTS, D), bf), ((D, D), bf), ((D, RANK), bf), ((RANK, D), bf)]),
    "tt_linear_q": (
        lambda x, w, s, a, b: ops.tt_linear_q(x, w, s, a, b, **NAT),
        [((4096, D), bf), ((D, F), i8), ((1, F), f32), ((D, RANK), bf),
         ((RANK, F), bf)]),
    "tt_linear_batched_a": (
        lambda x, w, a, b: ops.tt_linear_batched_a(x, w, a, b, **NAT),
        [((SLOTS, D), bf), ((D, F), bf), ((SLOTS, D, RANK), bf),
         ((RANK, F), bf)]),
    "tt_linear_batched_a_q": (
        lambda x, w, s, a, b: ops.tt_linear_batched_a_q(x, w, s, a, b,
                                                        **NAT),
        [((SLOTS, D), bf), ((D, F), i8), ((1, F), f32),
         ((SLOTS, D, RANK), bf), ((RANK, F), bf)]),
    "flash_attention": (
        lambda q, k, v: ops.flash_attention(q, k, v, **NAT),
        [((1, T, H, HD), bf)] * 3),
    "flash_attention_fwd": (
        lambda q, k, v: ops.flash_attention_fwd(q, k, v, **NAT),
        [((1, T, H, HD), bf)] * 3),
    "flash_attention_bwd": (
        lambda q, k, v, o, lse, g: ops.flash_attention_bwd(
            q, k, v, o, lse, g, **NAT),
        [((1, T, H, HD), bf)] * 4 + [((1, H, T), f32), ((1, T, H, HD), bf)]),
    "decode_attention": (
        lambda q, k, v, p: ops.decode_attention(q, k, v, p, **NAT),
        [((SLOTS, 1, H, HD), bf), ((SLOTS, KV, CACHE, HD), bf),
         ((SLOTS, KV, CACHE, HD), bf), ((SLOTS,), i32)]),
    "paged_decode_attention": (
        lambda q, k, v, t, p: ops.paged_decode_attention(q, k, v, t, p,
                                                         **NAT),
        [((SLOTS, CHUNK, H, HD), bf), (_POOL, bf), (_POOL, bf),
         ((SLOTS, PTAB), i32), ((SLOTS,), i32)]),
    "paged_decode_attention_int8": (
        lambda q, k, v, t, p, ks, vs: ops.paged_decode_attention(
            q, k, v, t, p, k_scale=ks, v_scale=vs, **NAT),
        [((SLOTS, CHUNK, H, HD), bf), (_POOL, i8), (_POOL, i8),
         ((SLOTS, PTAB), i32), ((SLOTS,), i32), (_SPOOL, f32),
         (_SPOOL, f32)]),
    # Kimi K2's latent attention: q/k head dim 192, v head dim 128, YaRN's
    # softmax scale
    "flash_attention_mla_fwd": (
        lambda q, k, v: ops.flash_attention_fwd(q, k, v, scale=MLA_SCALE,
                                                **NAT),
        [((1, T, MLA_H, MLA_QK), bf)] * 2 + [((1, T, MLA_H, MLA_V), bf)]),
    "flash_attention_mla_bwd": (
        lambda q, k, v, o, lse, g: ops.flash_attention_bwd(
            q, k, v, o, lse, g, scale=MLA_SCALE, **NAT),
        [((1, T, MLA_H, MLA_QK), bf)] * 2 + [((1, T, MLA_H, MLA_V), bf)] * 2
        + [((1, MLA_H, T), f32), ((1, T, MLA_H, MLA_V), bf)]),
    # the held experts' grouped matmuls at Kimi K2 widths: one chunk's rows
    # buffer, up and down projections, and the backward's transposed call
    "moe_gmm_up": (
        lambda x, w, g, u: ops.grouped_matmul(x, w, g, u, tm=128, **NAT),
        [((GMM_R, KIMI_D), bf), ((8, KIMI_D, KIMI_FF), bf),
         ((GMM_R // 128,), i32), ((1,), i32)]),
    "moe_gmm_down_transposed": (
        lambda x, w, g, u: ops.grouped_matmul(x, w, g, u, tm=128,
                                              transpose_w=True, **NAT),
        [((GMM_R, KIMI_D), bf), ((8, KIMI_FF, KIMI_D), bf),
         ((GMM_R // 128,), i32), ((1,), i32)]),
    "paged_decode_attention_serve": _paged_serve(CHUNK, bf),
    "paged_decode_attention_serve_int8": _paged_serve(CHUNK, i8),
    "paged_decode_attention_verify": _paged_serve(VERIFY_CHUNK, bf),
}


#: case -> the names its kernels' custom-call instructions carry (the
#: ``name=`` of each ``pallas_call``): the benchmark's roofline readers
#: find a kernel's device time by this name in a trace
KERNEL_NAMES = {
    "tt_linear_train": {"tt_linear"},
    "tt_linear_decode": {"tt_linear"},
    "tt_linear_q": {"tt_linear_w8"},
    "tt_linear_batched_a": {"tt_linear_batched_a"},
    "tt_linear_batched_a_q": {"tt_linear_batched_a_w8"},
    "flash_attention": {"flash_attention"},
    "flash_attention_fwd": {"flash_attention_fwd"},
    "flash_attention_bwd": {"flash_attention_bwd_dq",
                            "flash_attention_bwd_dkv"},
    "flash_attention_mla_fwd": {"flash_attention_fwd"},
    "flash_attention_mla_bwd": {"flash_attention_bwd_dq",
                                "flash_attention_bwd_dkv"},
    "moe_gmm_up": {"moe_gmm"},
    "moe_gmm_down_transposed": {"moe_gmm"},
    "decode_attention": {"decode_attention"},
    "paged_decode_attention": {"paged_decode_attention"},
    "paged_decode_attention_int8": {"paged_decode_attention"},
    "paged_decode_attention_serve": {"paged_decode_attention"},
    "paged_decode_attention_serve_int8": {"paged_decode_attention"},
    "paged_decode_attention_verify": {"paged_decode_attention"},
}


def _compiled_text(one_chip, name):
    fn, specs = CASES[name]
    return jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile().as_text()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    assert "tpu_custom_call" in _compiled_text(one_chip, name), name


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_name_in_compiled_hlo(one_chip, name):
    """Each kernel's instruction is named by its ``pallas_call``, not by
    whichever jitted wrapper calls it, so renaming a wrapper cannot
    silently empty a metric that finds the kernel by name."""
    got = {re.sub(r"\.\d+$", "", m.group(1))
           for m in re.finditer(r"^\s*(?:ROOT\s+)?%?(\S+) = .*"
                                r'custom_call_target="tpu_custom_call"',
                                _compiled_text(one_chip, name), re.M)}
    assert got == KERNEL_NAMES[name]


def test_chip_smoke_device_check_refuses_cpu():
    """chip_smoke.py never falls back to the CPU: its device check raises
    when JAX finds no TPU (the tests run with JAX_PLATFORMS=cpu)."""
    assert jax.default_backend() != "tpu"
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.require_tpu()
