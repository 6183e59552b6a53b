"""Compile rehearsal for a TPU v5e that is described, not attached: the
serving path's datapath kernels at phi4-mini-3.8b page-record width must
compile as real Mosaic kernels (``tpu_custom_call``), update the region
in place (temporaries below one record), and take regions larger than
2**31 elements. Nothing runs; shapes only.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under a
multi-worker run only the worker given this file does.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.kv_ingest.ops import kv_ingest
from repro.kernels.wr_scatter import ops as wr_ops

# one KV page of phi4-mini-3.8b: (layers, page_tokens, kv_heads, head_dim)
PAGE = (32, 16, 8, 128)
POOL_PAGES = 8 * (1024 // 16) + 1          # 8 slots x 1024 tokens + null
BIG_PAGES = 4100                           # 4100 x 524288 > 2**31 elements


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)


def _record_bytes(dtype):
    return int(np.prod(PAGE)) * jnp.dtype(dtype).itemsize


@pytest.mark.parametrize("n_pages", [POOL_PAGES, BIG_PAGES])
@pytest.mark.parametrize("entry", ["scatter_records", "kv_ingest"])
def test_page_scatter_compiles_in_place(one_chip, entry, n_pages):
    region = _shape(one_chip, (n_pages,) + PAGE, "bfloat16")
    vals = _shape(one_chip, (32,) + PAGE, "bfloat16")
    offs = _shape(one_chip, (32,), "int32")
    if entry == "scatter_records":
        lowered = wr_ops._scatter.lower(region, vals, offs, use_pallas=True)
    else:
        lowered = kv_ingest.lower(region, vals, offs)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < _record_bytes("bfloat16")
    assert mem.alias_size_in_bytes == n_pages * _record_bytes("bfloat16")


@pytest.mark.parametrize("rec,dtype", [((288, 256), "bfloat16"),
                                       ((4, 16), "float32"),
                                       ((2, 8), "int32")])
def test_two_dim_records_compile_in_place(one_chip, rec, dtype):
    region = _shape(one_chip, (64,) + rec, dtype)
    compiled = wr_ops._scatter.lower(
        region, _shape(one_chip, (4,) + rec, dtype),
        _shape(one_chip, (4,), "int32"), use_pallas=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < \
        int(np.prod(rec)) * jnp.dtype(dtype).itemsize


def test_page_gather_compiles_past_int32_elements(one_chip):
    """`gather_records` indexes whole records, so a region of more than
    2**31 elements compiles and no element index is ever built."""
    region = _shape(one_chip, (BIG_PAGES,) + PAGE, "bfloat16")
    length = int(np.prod(PAGE))
    compiled = wr_ops._gather.lower(
        region, _shape(one_chip, (32,), "int32"), length=length).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 32 * _record_bytes("bfloat16")


def test_page_gather_keeps_record_shape(one_chip):
    """Given the record shape, `gather_records` returns whole pages in
    that shape from the one `jit__gather` program: the block a
    device-to-device WRITE hands to the page scatter, with no
    temporary of a record's size."""
    region = _shape(one_chip, (POOL_PAGES,) + PAGE, "bfloat16")
    offs = _shape(one_chip, (32,), "int32")
    out = jax.eval_shape(
        lambda r, o: wr_ops._gather(r, o, length=PAGE),
        jax.ShapeDtypeStruct(region.shape, region.dtype),
        jax.ShapeDtypeStruct(offs.shape, offs.dtype))
    assert out.shape == (32,) + PAGE
    mem = wr_ops._gather.lower(region, offs, length=PAGE).compile() \
        .memory_analysis()
    assert mem.output_size_in_bytes == 32 * _record_bytes("bfloat16")
    assert mem.temp_size_in_bytes < _record_bytes("bfloat16")


def test_desc_ring_compiles_at_auto_depth(one_chip):
    """The device-resident descriptor ring at the TPU auto-residency
    depth (`DEVICE_RING_AUTO_DEPTH`), fused publish + poll."""
    from repro.core.notification import DEVICE_RING_AUTO_DEPTH
    from repro.kernels.desc_ring import ops as ring_ops
    cap = DEVICE_RING_AUTO_DEPTH["tpu"]
    i32 = _shape(one_chip, (), "int32")
    compiled = ring_ops._produce_consume.lower(
        _shape(one_chip, (cap, 16), "int32"),
        _shape(one_chip, (cap,), "uint8"),
        _shape(one_chip, (64, 16), "int32"), i32, i32).compile()
    assert compiled.memory_analysis().output_size_in_bytes > 0


def test_paged_decode_step_fits_one_chip(one_chip):
    """The paged decode step of phi4-mini-3.8b at its published widths,
    bf16, for a pod of 8 slots x 1024 tokens in 16-token pages: it
    compiles for v5e and its arguments, outputs and temporaries fit
    the chip's 16 GiB."""
    import types

    from repro.configs.base import get_config
    from repro.models.module import is_spec
    from repro.models.registry import build_model
    from repro.serve.paged import make_paged_step
    cfg = get_config("phi4-mini-3.8b")
    model = build_model(cfg)
    params = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch, max_seq, pt = 8, 1024, 16
    specs, treedef = jax.tree.flatten(model.cache_specs(batch, max_seq),
                                      is_leaf=is_spec)
    regions = [_shape(one_chip, (POOL_PAGES, s.shape[0], pt)
                      + tuple(s.shape[3:]), cfg.dtype) for s in specs]
    assert regions[0].shape[1:] == PAGE
    pool = types.SimpleNamespace(treedef=treedef, page_tokens=pt,
                                 pages_per_slot=max_seq // pt)
    compiled = make_paged_step(model, pool).lower(
        params, _shape(one_chip, (batch, 1), "int32"),
        _shape(one_chip, (batch, max_seq // pt), "int32"),
        _shape(one_chip, (batch,), "int32"), regions).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16 * 2**30, total
