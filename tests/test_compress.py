"""int8 compressed reduction: accuracy + wire-byte verification (subprocess
with 8 fake devices)."""
import os
import subprocess
import sys
import textwrap


def test_compressed_psum_accuracy_and_wire_bytes():
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.parallel.compress import compressed_psum_mean
        from repro.utils import hlo_cost

        mesh = make_mesh((8,), ("d",))
        F = 4096
        x = jax.random.normal(jax.random.PRNGKey(0), (8, F))

        def inner(x_l):
            return compressed_psum_mean(x_l[0], "d")[None]

        f = shard_map(inner, mesh=mesh, in_specs=P("d", None),
                      out_specs=P("d", None), check_vma=False)
        got = jax.jit(f)(x)
        exact = jnp.mean(x, axis=0)
        # every rank's result approximates the true mean
        err = float(jnp.abs(got - exact[None]).max())
        scale = float(jnp.abs(exact).max())
        assert err < 0.05 * scale, (err, scale)

        # wire bytes ~ int8: one a2a (F bytes) + one AG (F bytes) per dev
        c = jax.jit(f).lower(x).compile()
        wire = hlo_cost.analyze(c.as_text())["collective"]["wire_bytes"]
        f32_ar = 2 * F * 4 * 7 / 8
        assert wire < 0.55 * f32_ar, (wire, f32_ar)
        print("OK", err, wire, f32_ar)
    """)
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
