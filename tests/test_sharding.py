"""Sharded-execution tests (subprocess with 8 fake host devices so the main
pytest process keeps the single real CPU device)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH="src")


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=_ENV,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """The (data=4, model=2) sharded train step produces the same loss as
    the unsharded one — GSPMD + shard_map EP are numerically transparent."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_host_mesh
        from repro.configs import get_smoke_config
        from repro.config.base import RunConfig, SHAPES, TrainConfig
        from repro.models import model as M
        from repro.train import train_step as ts
        from repro.distributed import GradCompressor
        cfg = dataclasses.replace(get_smoke_config('granite-moe-1b-a400m'),
                                  num_experts=4, experts_per_token=2,
                                  moe_capacity_factor=8.0)
        run = RunConfig(model=cfg, shape=SHAPES['train_4k'],
                        adapter_kind='metatt', adapter_rank=4,
                        train=TrainConfig(remat='none'))
        spec = M.build_adapter_spec(run)
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, spec, key)
        toks = jax.random.randint(key, (8, 32), 0, cfg.vocab_size)
        # unsharded reference (compare CE to CE — total loss adds aux)
        _, m_ref = M.loss_fn(params['adapter'], params['base'],
                             params['frozen'], {'tokens': toks}, cfg, spec)
        l_ref = m_ref['ce']
        mesh = make_host_mesh(4, 2)
        with jax.set_mesh(mesh):
            state = ts.init_train_state(params['adapter'],
                                        GradCompressor('none'))
            step = ts.make_train_step(cfg, spec, run.optimizer, run.train,
                                      100)
            b = {'tokens': jax.device_put(
                toks, NamedSharding(mesh, P('data', None)))}
            state, mets = step(state, params['base'], params['frozen'], b)
        l_sh = float(mets['ce'])
        assert abs(l_sh - float(l_ref)) / float(l_ref) < 1e-2, (l_sh, float(l_ref))
        print('OK', l_sh, float(l_ref))
    """)


@pytest.mark.parametrize("scoring,dispatch", [("softmax", "capacity"),
                                               ("sigmoid", "dropless")])
def test_moe_ep_matches_local_path(scoring, dispatch):
    """shard_map expert parallelism == the no-mesh local path, exactly, for
    either router and dispatch; the counters agree too."""
    _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_host_mesh
        from repro.config.base import ModelConfig
        from repro.models import moe as MO
        from repro.models.layers import NO_ADAPTER
        key = jax.random.PRNGKey(0)
        cfg = ModelConfig(name='t', family='moe', num_layers=1, d_model=16,
                          num_heads=2, num_kv_heads=2, d_ff=8, vocab_size=32,
                          block_pattern=(('attn','moe'),), num_experts=4,
                          experts_per_token=2, moe_capacity_factor=8.0,
                          moe_scoring='{scoring}', moe_dispatch='{dispatch}',
                          param_dtype=jnp.float32, compute_dtype=jnp.float32)
        x = jax.random.normal(key, (4, 8, 16))
        w = {{'router': jax.random.normal(key, (16, 4)),
             'router_bias': jnp.zeros((4,)),
             'e_wg': jax.random.normal(key, (4, 16, 8)),
             'e_wu': jax.random.normal(jax.random.PRNGKey(1), (4, 16, 8)),
             'e_wd': jax.random.normal(jax.random.PRNGKey(2), (4, 8, 16))}}
        y_local, a_local = MO.moe_ffn(x, w, NO_ADAPTER, cfg)
        mesh = make_host_mesh(2, 4)   # model axis 4 -> 1 expert per shard
        with jax.set_mesh(mesh):
            y_ep, a_ep = jax.jit(lambda x, w: MO.moe_ffn(x, w, NO_ADAPTER,
                                                         cfg))(x, w)
        err = float(jnp.abs(y_local - y_ep).max())
        assert err < 1e-4, err
        for k in MO.COUNTERS:
            assert float(a_local[k]) == float(a_ep[k]), (k, a_local, a_ep)
        print('OK', err)
    """)


def test_elastic_remesh():
    """Reshard params from a (4,2) mesh to a (2,4) mesh (elastic resize)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.configs import get_smoke_config
        from repro.models import transformer
        from repro.distributed import remesh
        from repro.sharding import params_sharding
        cfg = get_smoke_config('gemma-7b')
        key = jax.random.PRNGKey(0)
        base = transformer.init_base_params(cfg, key)
        m1 = make_host_mesh(4, 2)
        base1 = jax.device_put(base, params_sharding(base, m1))
        m2 = make_host_mesh(2, 4)      # lost half the data axis, grew model
        base2 = remesh(base1, m2)
        for a, b in zip(jax.tree_util.tree_leaves(base1),
                        jax.tree_util.tree_leaves(base2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        print('OK')
    """)


def test_compressed_psum_shard_map():
    """int8-on-the-wire psum approximates the exact psum."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_host_mesh
        from repro.distributed import compressed_psum
        mesh = make_host_mesh(8, 1)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        def f(xs):
            exact = jax.lax.psum(xs, 'data')
            approx = compressed_psum(xs, 'data', kind='int8')
            return exact, approx
        with jax.set_mesh(mesh):
            ex, ap = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P('data', None),
                out_specs=(P(None, None), P(None, None)),
                check_vma=False))(x)
        rel = float(jnp.abs(ex - ap).max() / jnp.abs(ex).max())
        assert rel < 0.05, rel
        print('OK', rel)
    """)
