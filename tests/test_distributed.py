"""Sharded-execution equivalence tests (8 host devices via subprocess —
device count locks at first jax init, so multi-device tests isolate)."""
import subprocess
import sys
import textwrap

import pytest


def run_devices(body: str, n: int = 8):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax, jax.numpy as jnp
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SUBPROC_OK" in r.stdout
    return r.stdout


def test_moe_ep_matches_dense_oracle():
    run_devices("""
        from repro.models import ArchConfig
        from repro.models.moe import moe_ffn, moe_specs
        from repro.models.common import materialize
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_test_mesh
        cfg = ArchConfig(name='m', family='moe', n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=100,
                         n_experts=8, top_k=2, d_ff_expert=64,
                         capacity_factor=8.0, dtype=jnp.float32)
        p = materialize(moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64), jnp.float32)
        y_ref, aux_ref = moe_ffn(p, x, cfg)
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        with use_rules(mesh, "fsdp_sp"):
            y_ep, aux_ep = jax.jit(lambda p, x: moe_ffn(p, x, cfg))(p, x)
        assert float(jnp.abs(y_ref - y_ep).max()) < 1e-4
        assert abs(float(aux_ref) - float(aux_ep)) < 1e-5
    """)


@pytest.mark.parametrize("rules", ["fsdp_sp", "tp"])
def test_moe_dropless_sharded_matches_one_device(rules):
    """The serving (dropless) expert layer on a (pod, data, model) mesh,
    experts split over 'model': each shard computes the held experts it
    holds and the shards' sums agree with one device, for per-layer banks
    and for banks stacked over layers read at a layer index; the pick
    counts are the same."""
    run_devices(f"""
        from repro.models import ArchConfig
        from repro.models.moe import BANKS, moe_ffn, moe_specs
        from repro.models.common import materialize
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_test_mesh
        cfg = ArchConfig(name='m', family='moe', n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=100,
                         n_experts=8, top_k=2, d_ff_expert=64,
                         n_shared_experts=1, experts_held=(2, 6),
                         norm_topk_prob=False, dtype=jnp.float32)
        p = materialize(moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
        stacked = dict(p, layer=jnp.int32(1), **{{
            n: jnp.stack([-p[n], p[n]]) for n in BANKS}})
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64), jnp.float32)
        mask = jax.random.bernoulli(jax.random.PRNGKey(2), 0.7, (4, 8))
        f = jax.jit(lambda p, x, m: moe_ffn(p, x, cfg, token_mask=m))
        y_ref, n_ref = f(p, x, mask)
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        with use_rules(mesh, "{rules}"):
            for q in (p, stacked):
                y, n = f(q, x, mask)
                assert float(jnp.abs(y_ref - y).max()) < 1e-5
                assert (n == n_ref).all(), (n, n_ref)
        assert 0 < int(n_ref[0]) < int(n_ref[1]) == 2 * int(mask.sum())
    """)


def test_sharded_forward_matches_single_device():
    run_devices("""
        from repro.configs import get_config, reduce_config
        from repro.models import forward, init_params
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_test_mesh
        for arch in ("qwen3-8b", "zamba2-7b"):
            cfg = reduce_config(get_config(arch)).with_(dtype=jnp.float32)
            p = init_params(cfg, jax.random.PRNGKey(0))
            inp = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                     cfg.vocab_size)
            ref, _, _ = forward(p, inp, cfg, mode='train')
            mesh = make_test_mesh((2, 4), ("data", "model"))
            with use_rules(mesh, "fsdp_sp"):
                out, _, _ = jax.jit(
                    lambda p, x: forward(p, x, cfg, mode='train'))(p, inp)
            err = float(jnp.abs(ref - out).max() / (
                jnp.abs(ref).max() + 1e-9))
            assert err < 5e-3, (arch, err)
    """)


def test_sharded_decode_flash_combine():
    run_devices("""
        from repro.configs import get_config, reduce_config
        from repro.models import forward, init_params, init_cache
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_test_mesh
        cfg = reduce_config(get_config("qwen3-8b")).with_(dtype=jnp.float32)
        p = init_params(cfg, jax.random.PRNGKey(0))
        B, S = 4, 16
        inp = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                 cfg.vocab_size)
        cache = init_cache(cfg, B, S - 1)
        _, cache, _ = forward(p, inp[:, :S-1], cfg, cache=cache,
                              mode='prefill')
        ref, _, _ = forward(p, inp[:, S-1:], cfg, cache=cache,
                            mode='decode', pos=S-1)
        mesh = make_test_mesh((2, 4), ("data", "model"))
        with use_rules(mesh, "fsdp_sp"):
            cache2 = init_cache(cfg, B, S - 1)
            _, cache2, _ = jax.jit(lambda p, x, c: forward(
                p, x, cfg, cache=c, mode='prefill'))(p, inp[:, :S-1], cache2)
            out, _, _ = jax.jit(lambda p, x, c: forward(
                p, x, cfg, cache=c, mode='decode', pos=S-1))(
                p, inp[:, S-1:], cache2)
        err = float(jnp.abs(ref - out).max() / (jnp.abs(ref).max() + 1e-9))
        assert err < 5e-3, err
    """)


def test_dryrun_machinery_small_mesh():
    """The dry-run path (abstract params + shardings + compile +
    analyses) on an 8-device mesh with a reduced config."""
    run_devices("""
        from repro.configs import get_config, reduce_config
        from repro.distributed.sharding import use_rules, make_array_sharding
        from repro.launch.mesh import make_test_mesh
        from repro.launch import hlo_analysis
        from repro.models import param_specs
        from repro.models.common import ParamSpec, is_spec_tree_leaf
        from repro.train import make_train_step, abstract_train_state
        from repro.train.optim import OptState
        cfg = reduce_config(get_config("granite-moe-1b-a400m"))
        mesh = make_test_mesh((2, 4), ("data", "model"))
        with use_rules(mesh, "fsdp_sp"):
            def one(s):
                return jax.ShapeDtypeStruct(
                    s.shape, s.dtype or cfg.dtype,
                    sharding=make_array_sharding(s.shape, s.axes))
            pa = jax.tree.map(one, param_specs(cfg),
                              is_leaf=is_spec_tree_leaf)
            st = abstract_train_state(pa)
            f32 = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, jnp.float32, sharding=x.sharding), t)
            st = st._replace(opt=OptState(
                step=jax.ShapeDtypeStruct((), jnp.int32), master=f32(pa),
                m=f32(pa), v=f32(pa)))
            batch = {"inputs": jax.ShapeDtypeStruct((8, 16), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
            step = make_train_step(cfg)
            compiled = jax.jit(step, donate_argnums=0).lower(
                st, batch).compile()
            hlo = compiled.as_text()
            t = hlo_analysis.traffic_analysis(hlo)
            cb = hlo_analysis.collective_bytes(hlo)
            assert t["flops"] > 0 and t["hbm_bytes"] > 0
            assert cb["total"] > 0   # sharded training must communicate
    """)
