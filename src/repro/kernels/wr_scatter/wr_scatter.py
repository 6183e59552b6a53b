"""WRITE-run scatter kernel — the fused T4 flush.

A coalesced run of record WRITEs (an RDMA_WRITE chain, or a SEND run
landing in one posted MR) is ONE scatter: record rows stream through
VMEM while the destination offsets ride SMEM as a scalar-prefetched
"header". Each visited record block is overwritten in place; the region
itself never leaves HBM (`pl.ANY`) and the untouched remainder is
carried through input/output aliasing.

Each record is one block of its OWN trailing dims — ``(None, *rec)`` —
never a flattened ``(1, F)`` row: a flattened row breaks the TPU rule
that a block's last two dims divide by (8, 128) or equal the array's,
and reshaping the region to any other 2-D view is a relayout copy of
the whole region on TPU. So a region needs records of >= 2 dims
(``region.ndim >= 3``); `ops.scatter_records` routes anything else to
XLA.

Duplicate offsets are the CALLER's problem: the verbs layer dedupes
last-writer-wins (`dedupe_last_wins`) before launching, because a
revisited output block's ordering is unspecified here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(offs_ref, vals_ref, region_in_ref, out_ref):
    del offs_ref, region_in_ref
    out_ref[...] = vals_ref[...]


def wr_scatter(region, vals, offs, *, interpret=False):
    """region: (R, *rec) with len(rec) >= 2; vals: (m, *rec); offs: (m,)
    record indices. Returns the region with vals[i] written at record
    offs[i]."""
    rec = tuple(region.shape[1:])
    if len(rec) < 2:
        raise ValueError(
            f"wr_scatter needs records of >= 2 dims, got region "
            f"{region.shape}: route 1-D records through XLA")
    m = vals.shape[0]
    vals = vals.reshape((m,) + rec).astype(region.dtype)
    zeros = (0,) * len(rec)
    block = (None,) + rec
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m,),
        in_specs=[
            pl.BlockSpec(block, lambda i, offs: (i,) + zeros),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(block, lambda i, offs: (offs[i],) + zeros),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(region.shape, region.dtype),
        input_output_aliases={2: 0},       # region updated in place
        interpret=interpret,
    )(jnp.asarray(offs, jnp.int32), vals, region)
