"""End-to-end behaviour: train a tiny model, checkpoint it, serve it
through the FlexiNS stack (ring -> prefill -> transfer -> paged ingest ->
decode), and verify the costmodel/hlo_cost calibration."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# end-to-end train->checkpoint->serve + subprocess probes: tier-1 slow set
pytestmark = pytest.mark.slow

from repro.configs.base import get_config, reduced
from repro.models.registry import build_model
from repro.serve.engine import ServeEngine
from repro.train import data as data_lib
from repro.train import optimizer as optim
from repro.train.checkpoint import Checkpointer
from repro.train.train_loop import make_train_step
from repro.utils import hlo_cost


def test_train_checkpoint_serve_roundtrip(tmp_path):
    cfg = reduced(get_config("gemma-2b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt_cfg = optim.OptConfig(lr=2e-3, warmup_steps=2)
    opt_state = optim.init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(model, cfg, opt_cfg))
    for i in range(8):
        batch = data_lib.synthetic_batch(i, 2, 16, cfg.vocab_size)
        params, opt_state, metrics = step(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))

    ck = Checkpointer(str(tmp_path), async_write=True)
    ck.save(8, {"params": params})
    ck.wait()
    _, restored = ck.restore({"params": params})

    eng = ServeEngine(model, restored["params"], max_batch=2, max_seq=48)
    rid = eng.submit([3, 1, 4, 1, 5], max_new_tokens=5)
    out = eng.run_until_done()
    assert len(out[rid]) == 5
    assert all(0 <= t < cfg.vocab_size for t in out[rid])
    # the ring carried the request headers with batched DMA accounting
    assert eng.ring.dma_writes >= 1


def test_serve_cluster_example():
    """The ISSUE 10 walkthrough end to end: 2 prefill + 2 decode pods,
    one decode pod killed mid-run, every request completes via failover
    bit-exact vs the single-pod oracle (the example asserts all of it —
    a non-zero exit here is the cluster breaking, not the rig)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "examples",
                                      "serve_cluster.py")],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "EXACT" in r.stdout and "DIFFERS" not in r.stdout
    assert "killed mid-run" in r.stdout


def test_hlo_cost_parser_calibration():
    """The trip-count-aware parser equals known FLOPs for a scanned matmul
    chain — the calibration behind §Roofline's compute term."""
    D, L, B = 64, 7, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def f(x, w):
        def body(x, wl):
            return x @ wl, None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    compiled = jax.jit(f).lower(x, w).compile()
    res = hlo_cost.analyze(compiled.as_text())
    expected = 2 * B * D * D * L
    np.testing.assert_allclose(res["flops"], expected, rtol=0.05)
    # raw cost_analysis undercounts by ~L (the blind spot we fix)
    raw = compiled.cost_analysis().get("flops", 0.0)
    assert raw < 0.5 * expected


def test_hlo_cost_collectives_in_scan():
    """Collective bytes inside a scanned body are multiplied by the trip
    count (the MoE-dispatch-inside-layer-scan case)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.utils import hlo_cost

        mesh = make_mesh((4,), ("x",))
        L, N = 5, 1024

        def inner(x):
            return jax.lax.psum(x, "x")

        sm = shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)

        def f(x):
            def body(c, _):
                return sm(c), None
            y, _ = jax.lax.scan(body, x, None, length=L)
            return y.sum()

        x = jnp.ones((N,), jnp.float32)
        compiled = jax.jit(f).lower(x).compile()
        res = hlo_cost.analyze(compiled.as_text())
        wire = res["collective"]["wire_bytes"]
        one = 2 * (N * 4) * 3 / 4            # one AR wire bytes
        assert 0.8 * L * one <= wire <= 1.3 * L * one, (wire, L * one)
        print("OK")
    """)
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
