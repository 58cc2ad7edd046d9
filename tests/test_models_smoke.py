"""Per-architecture smoke tests (assignment deliverable f).

Every assigned architecture instantiates a REDUCED same-family config and
runs one forward + one train step on CPU, asserting output shapes and
finiteness. The FULL configs are exercised only via the dry-run
(ShapeDtypeStruct, no allocation) — see launch/dryrun.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as registry
from repro.config.base import SHAPES, RunConfig, TrainConfig
from repro.distributed import GradCompressor
from repro.models import model as model_lib
from repro.train import train_step as ts

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, b=2, t=16):
    batch = {"tokens": jax.random.randint(KEY, (b, t), 0, cfg.vocab_size)}
    if cfg.frontend == "patch_stub":
        batch["embeds"] = jax.random.normal(
            KEY, (b, cfg.frontend_seq, cfg.d_model), jnp.float32)
    if cfg.is_encdec:
        batch["enc_embeds"] = jax.random.normal(
            KEY, (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = registry.get_smoke_config(arch)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                    adapter_kind="metatt", adapter_rank=4,
                    train=TrainConfig(remat="none"))
    spec = model_lib.build_adapter_spec(run)
    params = model_lib.init_params(cfg, spec, KEY)
    batch = _batch_for(cfg)

    loss, metrics = model_lib.loss_fn(
        params["adapter"], params["base"], params["frozen"], batch, cfg,
        spec)
    assert np.isfinite(float(loss)), (arch, float(loss))
    assert float(loss) > 0

    step = ts.make_train_step(cfg, spec, run.optimizer, run.train,
                              total_steps=10)
    state = ts.init_train_state(params["adapter"], GradCompressor("none"))
    state, m = step(state, params["base"], params["frozen"], batch)
    assert np.isfinite(float(m["loss"]))
    assert float(m["grad_norm"]) > 0, f"{arch}: adapter got no gradient"
    # one more step with donated buffers
    state, m2 = step(state, params["base"], params["frozen"], batch)
    assert np.isfinite(float(m2["loss"]))


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL config must carry the exact assigned hyperparameters."""
    cfg = registry.get_config(arch)
    expected = {
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155, 32, 8),
        "kimi-k2-1t-a32b": (61, 7168, 64, 64, 2048, 163840, 384, 8),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216, 0, 0),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768, 0, 0),
        "stablelm-1.6b": (24, 2048, 32, 32, 5632, 100352, 0, 0),
        "gemma-7b": (28, 3072, 16, 16, 24576, 256000, 0, 0),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152, 0, 0),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866, 0, 0),
        "xlstm-125m": (12, 768, 4, 4, 0, 50304, 0, 0),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536, 16, 2),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size, cfg.num_experts, cfg.experts_per_token)
    assert got == expected, (arch, got, expected)


def test_long_context_skip_rules():
    for arch in registry.ARCH_IDS:
        cfg = registry.get_config(arch)
        runs = registry.supports_shape(cfg, "long_500k")
        assert runs == (cfg.family in ("ssm", "hybrid")), arch
        assert registry.supports_shape(cfg, "decode_32k")


def test_adapter_variants_on_roberta():
    """The paper's own target model with every adapter method."""
    cfg = registry.get_smoke_config("roberta-base")
    batch = _batch_for(cfg)
    for kind in ("metatt", "lora", "vera", "lotr"):
        run = RunConfig(model=cfg, shape=SHAPES["train_4k"],
                        adapter_kind=kind, adapter_rank=4)
        spec = model_lib.build_adapter_spec(run)
        params = model_lib.init_params(cfg, spec, KEY)
        loss, _ = model_lib.loss_fn(params["adapter"], params["base"],
                                    params["frozen"], batch, cfg, spec)
        assert np.isfinite(float(loss)), kind


def test_kimi_param_count_is_about_1t():
    """The headline: the kimi config really is ~1T parameters (counted via
    eval_shape — never allocated)."""
    cfg = registry.get_config("kimi-k2-1t-a32b")
    from repro.models import transformer
    sds = jax.eval_shape(
        lambda: transformer.init_base_params(cfg, KEY))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(sds))
    assert 0.9e12 < n < 1.3e12, n
