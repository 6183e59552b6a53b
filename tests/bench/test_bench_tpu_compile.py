"""Each configuration's cell shapes compile for a TPU v5e that is
described, not attached, and fit its memory: the paged decode step of a
decode pod at the configuration's slots and context, and the prefill of
the largest bucket. Nothing runs; shapes only.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under a
multi-worker run only the worker given this file does.
"""
import json
import os
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = sorted(p.name for p in (ROOT / "bench" / "configs").glob("*.json"))
HBM = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _model_and_params(name, one_chip):
    from bench import harness
    cfg = json.loads((ROOT / "bench" / "configs" / name).read_text())
    model = harness.program_model(cfg)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return cfg, model, params


def _total(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("name", CONFIGS)
def test_paged_decode_step_fits_v5e(one_chip, name):
    from repro.models.module import is_spec
    from repro.serve.paged import make_paged_step
    cfg, model, params = _model_and_params(name, one_chip)
    dep = cfg["deployment"]
    batch, max_seq, pt = (dep["slots_per_decode_pod"], dep["max_seq"],
                          dep["page_tokens"])
    specs, treedef = jax.tree.flatten(model.cache_specs(batch, max_seq),
                                      is_leaf=is_spec)
    n_pages = batch * (max_seq // pt) + 1
    regions = [jax.ShapeDtypeStruct(
        (n_pages, s.shape[0], pt) + tuple(s.shape[3:]),
        jnp.dtype(cfg["torch_dtype"]), sharding=one_chip) for s in specs]
    pool = types.SimpleNamespace(treedef=treedef, page_tokens=pt,
                                 pages_per_slot=max_seq // pt)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = make_paged_step(model, pool).lower(
        params, i32(batch, 1), i32(batch, max_seq // pt), i32(batch),
        regions).compile()
    assert _total(compiled) < HBM


@pytest.mark.parametrize("name", CONFIGS)
def test_largest_prefill_bucket_fits_v5e(one_chip, name):
    cfg, model, params = _model_and_params(name, one_chip)
    S = cfg["deployment"]["max_seq"]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.prefill).lower(
        params, i32(1, S), last_pos=i32(1)).compile()
    assert _total(compiled) < HBM
