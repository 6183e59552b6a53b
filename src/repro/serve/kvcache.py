"""Paged KV cache pool + cache padding utilities.

The pool holds fixed-size pages; sequences own logical page ranges through
the core.shadow table (the paper's shadow memory region). Transferred
prefill caches are *ingested* page-by-page (core.rx_engine / the kv_ingest
kernel) and *gathered* back to the contiguous layout the decode step
consumes.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rx_engine
from repro.core.shadow import ShadowTable


def pad_caches(caches, s_prefill: int, s_max: int):
    """Pad layer-stacked decode caches from prefill length to max length.

    Only sequence-indexed leaves (dim 2 == s_prefill under the (L, B, S, …)
    stacking) are padded; window/state/conv caches pass through."""
    if s_prefill == s_max:
        return caches

    def pad(a):
        if a.ndim >= 3 and a.shape[2] == s_prefill:
            pw = [(0, 0)] * a.ndim
            pw[2] = (0, s_max - s_prefill)
            return jnp.pad(a, pw)
        return a

    return jax.tree.map(pad, caches)


@dataclass
class SeqAllocation:
    seq_id: int
    region: str
    logical_pages: np.ndarray


class PagedKVPool:
    """One pool per (layer-stack leaf); pages: (n_pages, page_tokens, ...)."""

    def __init__(self, n_pages: int, page_tokens: int, feature_shape: tuple,
                 dtype="bfloat16"):
        self.page_tokens = page_tokens
        self.pages = jnp.zeros((n_pages, page_tokens) + tuple(feature_shape),
                               jnp.dtype(dtype))
        self.shadow = ShadowTable(n_pages)
        self._next_id = 0

    def allocate(self, n_tokens: int) -> SeqAllocation:
        n_pages = -(-n_tokens // self.page_tokens)
        name = f"seq{self._next_id}"
        region = self.shadow.register_region(name, n_pages, self.page_tokens)
        self._next_id += 1
        logical = np.arange(region.base_logical,
                            region.base_logical + n_pages)
        return SeqAllocation(self._next_id - 1, name, logical)

    def free(self, alloc: SeqAllocation):
        self.shadow.release_region(alloc.region)

    def ingest(self, alloc: SeqAllocation, kv: jnp.ndarray,
               use_kernel: bool = False, interpret: bool = False):
        """kv: (S, ...) contiguous prefill output -> paged pool (T2 path)."""
        S = kv.shape[0]
        n_pages = len(alloc.logical_pages)
        pad = n_pages * self.page_tokens - S
        if pad:
            kv = jnp.pad(kv, [(0, pad)] + [(0, 0)] * (kv.ndim - 1))
        tiles = kv.reshape((n_pages, self.page_tokens) + kv.shape[1:])
        self.pages = rx_engine.ingest(self.pages, tiles, alloc.logical_pages,
                                      self.shadow, use_kernel=use_kernel,
                                      interpret=interpret)

    def gather(self, alloc: SeqAllocation, n_tokens: int) -> jnp.ndarray:
        tiles = rx_engine.gather_pages(self.pages, alloc.logical_pages,
                                       self.shadow)
        flat = tiles.reshape((-1,) + tiles.shape[2:])
        return flat[:n_tokens]
