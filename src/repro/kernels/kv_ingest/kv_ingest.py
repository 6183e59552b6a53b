"""Paged KV-cache ingest — the FlexiNS RX path itself (T2).

Incoming payload tiles (one KV page each) are scattered into the paged
cache at physical page ids resolved by the shadow table. That is exactly
the fused WRITE-run scatter: the page id stream is scalar-prefetched
(the "header" rides SMEM, the payload rides the double-buffered VMEM
stream), each visited page block is overwritten in place and the rest of
the cache is carried through input/output aliasing, so no byte of the
(unbounded) working set is ever resident beyond the two in-flight tiles.
"""
from __future__ import annotations

from repro.kernels.wr_scatter.wr_scatter import wr_scatter


def kv_ingest(pages, payload, page_ids, *, interpret=False):
    """pages: (P, T, F...); payload: (n, T, F...); page_ids: (n,) int32.

    Returns updated pages; duplicate ids are caller error (shadow table
    allocates unique physical pages)."""
    return wr_scatter(pages, payload, page_ids, interpret=interpret)
