"""Sharded-semantics tests. These need >1 device, so each runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
main test process keeps the real single CPU device per the brief)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every test here spawns an 8-fake-device subprocess: tier-1 slow set
pytestmark = pytest.mark.slow


def run_sharded(body: str, timeout=600):
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.parallel import sharding
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_moe_a2a_matches_local_oracle():
    run_sharded("""
        from repro.configs.base import get_config, reduced
        from repro.models import moe
        from repro.models.module import init_params
        import repro.perf as perf

        cfg = reduced(get_config("granite-moe-1b-a400m"))
        params = init_params(moe.moe_spec(cfg), jax.random.PRNGKey(0), "float32")
        x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        y_local, _ = moe.moe_apply(params, x, cfg)       # no mesh: local oracle

        mesh = make_mesh((2, 4), ("data", "model"))
        perf.set_flags(capacity_factor=8.0)              # no drops: exact match
        with sharding.use_mesh(mesh, fsdp=False):
            y_a2a, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)
        perf.set_flags(moe_impl="replicated")
        with sharding.use_mesh(mesh, fsdp=False):
            y_rep, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)
        np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_local),
                                   atol=2e-4, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(y_rep), np.asarray(y_local),
                                   atol=2e-4, rtol=2e-3)
        print("OK")
    """)


def test_moe_a2a_with_fsdp_weights():
    run_sharded("""
        from repro.configs.base import get_config, reduced
        from repro.models import moe
        from repro.models.module import init_params
        import repro.perf as perf

        cfg = reduced(get_config("granite-moe-1b-a400m"))
        params = init_params(moe.moe_spec(cfg), jax.random.PRNGKey(0), "float32")
        x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        y_local, _ = moe.moe_apply(params, x, cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        perf.set_flags(capacity_factor=8.0)
        with sharding.use_mesh(mesh, fsdp=True):
            sh = sharding.param_shardings(moe.moe_spec(cfg))
            p_shard = jax.tree.map(
                lambda a, s: jax.device_put(a, s) if s is not None else a,
                params, sh)
            y, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(p_shard, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_local),
                                   atol=2e-4, rtol=2e-3)
        print("OK")
    """)


def test_context_parallel_attention_matches_local():
    run_sharded("""
        from repro.parallel import collectives
        from repro.models.attention import chunked_attention

        B, S, KVH, G, Dk = 2, 64, 1, 3, 16      # H=3 not divisible by 4 -> CP
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, KVH, G, Dk))
        k = jax.random.normal(ks[1], (B, S, KVH, Dk))
        v = jax.random.normal(ks[2], (B, S, KVH, Dk))
        exp = chunked_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16)
        mesh = make_mesh((2, 4), ("data", "model"))
        with sharding.use_mesh(mesh):
            got = jax.jit(lambda q, k, v: collectives.attend(
                q, k, v, causal=True, q_chunk=16, kv_chunk=16))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)
        print("OK")
    """)


def test_seqparallel_decode_matches_local():
    run_sharded("""
        from repro.parallel import collectives

        B, S, KVH, G, Dk = 4, 32, 2, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, KVH, G, Dk))
        kc = jax.random.normal(ks[1], (B, S, KVH, Dk))
        vc = jax.random.normal(ks[2], (B, S, KVH, Dk))
        kn = jax.random.normal(ks[3], (B, KVH, Dk))
        vn = jax.random.normal(ks[4], (B, KVH, Dk))
        pos = jnp.array([31, 7, 16, 0], jnp.int32)
        exp, ek, ev = collectives.seqparallel_decode_attention(
            q, kc, vc, kn, vn, pos)          # no mesh: local path
        mesh = make_mesh((2, 4), ("data", "model"))
        with sharding.use_mesh(mesh):
            got, gk, gv = jax.jit(collectives.seqparallel_decode_attention)(
                q, kc, vc, kn, vn, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(ek), atol=1e-6)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(ev), atol=1e-6)
        print("OK")
    """)


def test_tx_engine_pod_transfer_and_spray():
    run_sharded("""
        from repro.core import tx_engine
        from repro.core.descriptors import TransferPlan
        from repro.models.module import Spec
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        x = jnp.arange(2 * 8 * 16, dtype=jnp.float32).reshape(2, 8, 16)
        spec = Spec((2, 8, 16), ("batch", "kv_seq", None))
        with sharding.use_mesh(mesh):
            x_dev = jax.device_put(x, NamedSharding(mesh, P(("pod",), None, None)))
            plan = TransferPlan(axis="pod", shift=1)
            y = jax.jit(lambda t: tx_engine.transmit(
                {"k": t}, {"k": spec}, plan))(x_dev)["k"]
            # pod axis has size 2: shift swaps the two pod-halves of batch
            exp = np.concatenate([np.asarray(x)[1:], np.asarray(x)[:1]])
            np.testing.assert_allclose(np.asarray(y), exp)
            # staged baseline: same values
            y2 = jax.jit(lambda t: tx_engine.transmit_staged(
                {"k": t}, {"k": spec}, plan))(x_dev)["k"]
            np.testing.assert_allclose(np.asarray(y2), exp)
            # quantized wire: close values
            plan8 = TransferPlan(axis="pod", shift=1, quantize_bits=8)
            y3 = jax.jit(lambda t: tx_engine.transmit(
                {"k": t}, {"k": spec}, plan8))(x_dev)["k"]
            np.testing.assert_allclose(np.asarray(y3), exp, rtol=0.02,
                                       atol=0.02 * np.abs(exp).max())
        print("OK")
    """)


def test_moe_ep_over_data_and_seq_parallel_match_oracle():
    """The beyond-paper EP=(model x data) sharding and Megatron-SP residual
    must not change numerics."""
    run_sharded("""
        from repro.configs.base import get_config, reduced
        from repro.models import moe
        from repro.models.module import init_params
        import repro.perf as perf

        cfg = reduced(get_config("granite-moe-1b-a400m"))
        # reduced cfg has 4 experts; (model=2 x data=2) = 4 -> 1 expert/dev
        params = init_params(moe.moe_spec(cfg), jax.random.PRNGKey(0), "float32")
        x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        y_local, _ = moe.moe_apply(params, x, cfg)
        mesh = make_mesh((2, 2), ("data", "model"))
        perf.set_flags(capacity_factor=8.0, ep_over_data=True)
        try:
            with sharding.use_mesh(mesh, fsdp=False):
                y1, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)
            perf.set_flags(moe_impl="replicated")
            with sharding.use_mesh(mesh, fsdp=False):
                y2, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)
        finally:
            perf.reset_flags()
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y_local),
                                   atol=2e-4, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y_local),
                                   atol=2e-4, rtol=2e-3)
        print("OK")
    """)


def test_seq_parallel_forward_matches_local():
    run_sharded("""
        from repro.configs.base import get_config, reduced
        from repro.models.registry import build_model
        import repro.perf as perf

        cfg = reduced(get_config("granite-moe-1b-a400m"))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                    cfg.vocab_size)
        exp, _ = model.forward(params, tokens)
        mesh = make_mesh((2, 4), ("data", "model"))
        perf.set_flags(seq_parallel=True, capacity_factor=8.0)
        try:
            with sharding.use_mesh(mesh, fsdp=False):
                got, _ = jax.jit(lambda p, t: model.forward(p, t))(params, tokens)
        finally:
            perf.reset_flags()
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=2e-3, rtol=2e-3)
        print("OK")
    """)


@pytest.mark.parametrize("arch", ["gemma-2b", "granite-moe-1b-a400m",
                                  "deepseek-v3-671b", "mamba2-780m",
                                  "recurrentgemma-2b", "whisper-base"])
def test_reduced_train_step_lowers_on_mesh(arch):
    """Reduced config of each family lowers+compiles on a (2,2,2) mesh."""
    run_sharded(f"""
        from repro.configs.base import get_config, reduced, ShapeConfig
        from repro.models.registry import build_model, input_specs
        from repro.train import optimizer as optim
        from repro.train.train_loop import make_train_step

        cfg = reduced(get_config("{arch}"))
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        with sharding.use_mesh(mesh):
            model = build_model(cfg)
            specs = model.param_specs()
            params = sharding.abstract_with_shardings(specs, cfg.dtype)
            shape = ShapeConfig("t", 32, 4, "train")
            ins = input_specs(cfg, shape)
            opt_cfg = optim.OptConfig()
            opt = sharding.abstract_with_shardings(
                optim.opt_state_specs(specs, opt_cfg), "float32")
            step = make_train_step(model, cfg, opt_cfg)
            compiled = jax.jit(step).lower(params, opt, dict(ins)).compile()
            assert compiled.cost_analysis().get("flops", 0) > 0
        print("OK")
    """)
