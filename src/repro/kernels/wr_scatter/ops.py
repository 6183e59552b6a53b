"""Fused-launch ops for the T4 flush: ONE compiled launch per run.

A coalesced flush lands its WRITE run through `scatter_records` and its
READ run through `gather_records` — each is a single jitted call.
Launches are counted in the `fused/launches` registry counter — the
launches-per-flush contract the line-rate bench gates.

Which scatter runs is a fixed rule, not a runtime fallback:

  * records of >= 2 dims (``region.ndim >= 3``, e.g. a KV page
    ``(layers, page_tokens, kv_heads, head_dim)``) take the Pallas
    `wr_scatter` kernel on TPU: each record is one block of its own
    trailing dims, aliased in place;
  * 1-D records (descriptor rows, byte blocks) cannot be a TPU block —
    a ``(1, F)`` block breaks the (8, 128) tiling rule — so they take
    the donated XLA ``at[].set`` on every backend;
  * off TPU everything takes ``at[].set``: interpret-mode Pallas walks
    the grid in Python, which is exactly the per-element cost this
    family exists to delete.

Two datapath-specific contracts live here, not in the kernel:

  * Shape bucketing — run lengths are ragged, so offsets/values pad to
    the next power of two by repeating the trailing (offset, value)
    pair. A duplicate scatter index carrying an identical value retires
    deterministically whatever order XLA picks, and a duplicate gather
    index is just read twice (callers slice the true prefix) — the jit
    cache stays warm instead of recompiling per run length.
  * Donation — `scatter_records` donates the region buffer: the engine
    immediately rebinds the result as the region, and every reader
    (`pd.mr_array`, handlers) refetches from the engine per call, so no
    live reference aliases the donated buffer.

Only the batch-wise flush (`coalesce_writes=True`) calls these: the
element-at-a-time oracle never compiles.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.wr_scatter import ref
from repro.kernels.wr_scatter.wr_scatter import wr_scatter as _pallas_scatter
from repro.obs import metrics


# compiles as `jit__scatter`, `_gather` below as `jit__gather`: the
# benchmark's landing roofline finds their device time by those names
@partial(jax.jit, static_argnames=("use_pallas",), donate_argnums=(0,))
def _scatter(region, vals, offs, *, use_pallas=False):
    if use_pallas:
        return _pallas_scatter(region, vals, offs)
    return region.at[offs].set(jnp.asarray(vals).astype(region.dtype))


@partial(jax.jit, static_argnames=("length",))
def _gather(region, offs, *, length):
    # whole records by row index: no flat element index, so a region of
    # more than 2**31 elements cannot wrap an int32 index. `length` is an
    # element count (flat rows) or a record shape (rows in that shape):
    # the reshape lives in this program, never in an eager op after it
    rec = tuple(length) if isinstance(length, tuple) else (length,)
    if math.prod(region.shape[1:]) != math.prod(rec):
        region = region.reshape((-1,) + rec)
    rows = jnp.take(region, offs, axis=0)
    if rows.shape[1:] != rec:
        rows = rows.reshape((offs.shape[0],) + rec)
    return rows


_ON_TPU: bool | None = None


def _use_pallas(region) -> bool:
    global _ON_TPU
    if _ON_TPU is None:         # backend probe once, not per launch
        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU and region.ndim >= 3


def _count():
    metrics.get_registry().scope("fused").counter("launches").inc()


def _bucket(m: int) -> int:
    return 1 << max(0, m - 1).bit_length()


def scatter_records(region, offs, vals):
    """ONE fused, donated scatter: region[offs[i]] <- vals[i] rows.
    offs is 1-D with vals row-aligned (`dedupe_last_wins` upstream). A
    host `vals` converts to the device at this call; a device `vals`
    lands as it is."""
    offs = np.asarray(offs, np.int32).ravel()
    m = offs.size
    b = _bucket(m)
    if b != m and isinstance(vals, np.ndarray):
        # device-array sources skip bucketing: padding one would be an
        # eager device op (the transport's MR-sourced WRITE blocks arrive
        # already bucketed by `gather_records`, offsets padded to match)
        offs = np.concatenate([offs, np.repeat(offs[-1:], b - m)])
        vals = np.concatenate([vals, np.repeat(vals[-1:], b - m, axis=0)])
    _count()
    return _scatter(region, vals, offs, use_pallas=_use_pallas(region))


def scatter_one(region, offsets, buf):
    """One DmaOp's scatter as a fused launch. Well-formed record writes
    (1-D offsets, row-aligned buf) ride `scatter_records`; the general
    broadcasting form keeps `at[].set` semantics verbatim (offsets shape
    included) inside one jitted launch — pallas needs row alignment."""
    offsets = np.asarray(offsets, np.int32)
    if offsets.ndim == 1 and getattr(buf, "ndim", 0) >= 1 \
            and buf.shape[0] == offsets.size:
        return scatter_records(region, offsets, buf)
    _count()
    return _scatter(region, buf, offsets, use_pallas=False)


def gather_records(region, offs, length):
    """ONE fused gather of records at record offsets `offs`. `length` is
    the record's element count, giving a (padded_n, length) block, or
    its shape, giving a (padded_n, *shape) block. Callers slice the true
    prefix rows (the pad tail re-reads the last record)."""
    offs = np.asarray(offs, np.int32).ravel()
    n = offs.size
    b = _bucket(n)
    if b != n:
        offs = np.concatenate([offs, np.repeat(offs[-1:], b - n)])
    _count()
    length = tuple(int(d) for d in length) if isinstance(
        length, (tuple, list)) else int(length)
    return _gather(region, offs, length=length)


reference = ref.reference
reference_gather = ref.reference_gather
