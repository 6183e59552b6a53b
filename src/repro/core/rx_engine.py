"""T2 — unlimited-working-set in-cache processing RX path.

`ingest` scatters incoming KV payload tiles into the paged cache through
the logical->physical shadow table. On TPU the scatter runs as the
kernels/kv_ingest Pallas kernel whose BlockSpec double-buffering pins VMEM
residency to two tiles regardless of cache size (the "there is always an
invalidated cacheline" invariant); without the kernel it is a jnp scatter
with the same semantics (the kernel's ref oracle). The kernel runs in
interpret mode only when the caller asks for it (`interpret=True`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.shadow import ShadowTable


def ingest(pages, payload, logical_ids, shadow: ShadowTable | None = None,
           *, use_kernel: bool = False, interpret: bool = False):
    """pages: (n_pages, page_tokens, KVH, hd); payload: (n, page_tokens,
    KVH, hd); logical_ids: (n,) page ids (logical if shadow given)."""
    ids = np.asarray(logical_ids)
    if shadow is not None:
        ids = shadow.translate(ids)
    ids = jnp.asarray(ids, jnp.int32)
    if use_kernel:
        from repro.kernels.kv_ingest.ops import kv_ingest
        return kv_ingest(pages, payload, ids, interpret=interpret)
    return pages.at[ids].set(payload.astype(pages.dtype))


def gather_pages(pages, logical_ids, shadow: ShadowTable | None = None):
    """Read back a sequence's pages in logical order -> contiguous KV."""
    ids = np.asarray(logical_ids)
    if shadow is not None:
        ids = shadow.translate(ids)
    return jnp.take(pages, jnp.asarray(ids, jnp.int32), axis=0)
