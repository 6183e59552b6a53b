"""Paged KV cache as DMA-registered memory (ISSUE 10 tentpole a).

The dense serve cache — one ``(layers, max_batch, max_seq, ...)`` array
per leaf, installed slot-by-slot — becomes a `PagePool`: fixed-size KV
*pages* (``page_tokens`` rows of every layer of one cache leaf) held in
per-leaf page arrays registered as verbs MRs on the owning pod's
protection domain. Cache state IS engine DMA memory:

  * one-sided RDMA_WRITEs from a prefill pod land pages directly in the
    pool (``KVTransferEngine.migrate_pages`` — the record unit of the MR
    is exactly one page, so a leaf's run of page writes is one fused
    gather whose device block lands in one scatter, never on the host);
  * the decode step reads pages through a slot -> page-table
    indirection (`make_paged_step`): gather pages into the dense
    layout, run `model.decode_step`, scatter the updated pages back —
    all inside ONE jitted body, no host sync.

Page 0 is the *null page*: table entries of inactive slots (and the
unallocated tail of short sequences) point at it. Its contents are
garbage by design — every row it backs sits at a position the decode
attention masks (``kvp <= pos``), so the masked lanes contribute exact
zeros and paged decode stays bit-exact with the dense oracle.

Eligibility is probed, not assumed: paging (and prompt-length
bucketing) require every cache leaf to be sequence-indexed — true for
attention/MLA stacks, false for rec/ssm state caches (prefilling a
padded prompt would corrupt the state) and window caches (the rotation
index depends on the prefill length). `pageable` / `bucketable` decide;
ineligible models keep the dense path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.module import is_spec
from repro.obs import metrics


def bucket_len(n: int, max_len: int) -> int:
    """Power-of-two bucket for a prompt length (capped at max_len): the
    prefill jit cache holds O(log max_len) entries instead of one per
    distinct prompt length."""
    if n < 1:
        raise ValueError(f"prompt length must be >= 1, got {n}")
    p = 1
    while p < n:
        p <<= 1
    return min(p, max_len)


def _spec_shapes(model, batch: int, seq: int) -> list[tuple]:
    leaves = jax.tree.leaves(model.cache_specs(batch, seq), is_leaf=is_spec)
    return [tuple(s.shape) for s in leaves]


def seq_indexed_only(model, probes: tuple[int, int] = (16, 24)) -> bool:
    """True iff EVERY cache leaf is sequence-indexed under the stacked
    ``(layers, batch, seq, ...)`` layout. Probed at two distinct seq
    values so a coincidental dimension (a window W == probe, a state
    width) cannot masquerade as the seq axis."""
    a, b = (_spec_shapes(model, 2, s) for s in probes)
    if not a or len(a) != len(b):
        return False
    for sa, sb in zip(a, b):
        if len(sa) < 3 or len(sa) != len(sb):
            return False
        if sa[2] != probes[0] or sb[2] != probes[1]:
            return False
        if any(x != y for i, (x, y) in enumerate(zip(sa, sb)) if i != 2):
            return False
    return True


def pageable(model) -> bool:
    """Paged KV is exact only when the whole cache is seq-indexed (and
    the arch has no windowed/rotating layers — hybrids carry both)."""
    return getattr(model.cfg, "hybrid", None) is None \
        and seq_indexed_only(model)


def bucketable(model) -> bool:
    """Bucketed (right-padded) prefill is exact under `pageable`'s
    conditions PLUS no MoE: expert capacity depends on the total token
    count, so padding could change which tokens drop."""
    return pageable(model) and getattr(model.cfg, "moe", None) is None


class PagePool:
    """Fixed-size KV pages for one serving pod, registered as MRs.

    One page array per cache leaf, shaped ``(n_pages, layers,
    page_tokens, *feat)`` — an MR *record* is one page, so page ids are
    record offsets and one-sided verbs address pages directly. Page ids
    are shared across leaves: an allocation is one id list, valid in
    every leaf's region. The slot -> page table (``(max_batch,
    pages_per_slot)`` int32, 0 = null page) is the indirection the paged
    decode step consumes. `device` places the page regions on one chip
    (None: the default device)."""

    pages_allocated = metrics.counter_attr()
    pages_freed = metrics.counter_attr()

    def __init__(self, model, pd, *, max_batch: int, max_seq: int,
                 page_tokens: int = 16, n_pages: int | None = None,
                 device=None):
        metrics.instance_scope(self, "pagepool", indexed=True)
        if max_seq % page_tokens:
            raise ValueError(
                f"max_seq={max_seq} must be a multiple of "
                f"page_tokens={page_tokens}")
        self.model = model
        self.pd = pd
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.page_tokens = page_tokens
        self.pages_per_slot = max_seq // page_tokens
        # +1 for the null page: a full pool can still back every slot
        self.n_pages = n_pages if n_pages is not None else \
            max_batch * self.pages_per_slot + 1
        self.pages_allocated = 0
        self.pages_freed = 0
        cfg_dtype = model.cfg.dtype
        specs, self.treedef = jax.tree.flatten(
            model.cache_specs(max_batch, max_seq), is_leaf=is_spec)
        self.mrs = []
        idx = metrics.scope_of(self).name     # pagepool{i}: unique MR names
        for i, spec in enumerate(specs):
            shp = tuple(spec.shape)           # (L, B, S, *feat)
            page_shape = (self.n_pages, shp[0], page_tokens) + shp[3:]
            arr = jnp.zeros(page_shape, jnp.dtype(spec.dtype or cfg_dtype),
                            device=device)
            self.mrs.append(self.pd.reg_mr(f"{idx}/leaf{i}", arr))
        self._free = list(range(self.n_pages - 1, 0, -1))   # page 0 = null
        self.table = np.zeros((max_batch, self.pages_per_slot), np.int32)

    # -- allocation ---------------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_tokens)

    def alloc(self, n: int) -> np.ndarray:
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: want {n}, have {len(self._free)}")
        self.pages_allocated += n
        return np.asarray([self._free.pop() for _ in range(n)], np.int64)

    def free(self, ids) -> None:
        ids = np.asarray(ids, np.int64).ravel()
        self.pages_freed += int(ids.size)
        self._free.extend(int(i) for i in ids)

    def bind_slot(self, slot: int, ids) -> None:
        """Point a slot's table row at its pages (tail stays null)."""
        ids = np.asarray(ids, np.int64).ravel()
        self.table[slot, :] = 0
        self.table[slot, :ids.size] = ids

    def clear_slot(self, slot: int) -> np.ndarray:
        """Unbind and return the slot's pages (caller frees them)."""
        row = self.table[slot]
        ids = row[row != 0].astype(np.int64)
        self.table[slot, :] = 0
        return ids

    # -- host-local page writes (the prefill pod filling its own pool) ------
    def fill(self, ids, caches) -> None:
        """Write one sequence's prefill caches (batch 1, any seq length)
        into `ids`: leaf rows are re-tiled to ``(k, L, page_tokens,
        *feat)`` pages and land by direct region rebind — host-local
        writes don't ride the wire."""
        ids = np.asarray(ids, np.int64).ravel()
        k = int(ids.size)
        for mr, rows in zip(self.mrs, self.page_rows(caches, k)):
            region = jnp.asarray(self.pd.mr_array(mr))
            self.pd.engine.regions[mr.name] = \
                region.at[jnp.asarray(ids)].set(rows.astype(region.dtype))

    def page_rows(self, caches, k: int) -> list:
        """Each leaf of a batch-1 cache tree as ``(k, L, page_tokens,
        *feat)`` page records (padded / truncated to k pages) — the
        shape an MR record write expects."""
        need = k * self.page_tokens
        out = []
        for leaf in jax.tree.leaves(caches):
            x = jnp.asarray(leaf)[:, 0]       # (L, S, *feat)
            S = x.shape[1]
            if S < need:
                pw = [(0, 0)] * x.ndim
                pw[1] = (0, need - S)
                x = jnp.pad(x, pw)
            else:
                x = x[:, :need]
            x = x.reshape((x.shape[0], k, self.page_tokens) + x.shape[2:])
            out.append(jnp.moveaxis(x, 1, 0))
        return out

    # -- migration lease ----------------------------------------------------
    def lease(self, ids) -> list[tuple]:
        """The remote half of a migration: ``(rkey, page_ids)`` per leaf
        region, in leaf order — what a prefill pod needs to RDMA_WRITE
        pages into THIS pool."""
        ids = np.asarray(ids, np.int64).ravel()
        return [(mr.rkey, ids) for mr in self.mrs]

    # -- device views --------------------------------------------------------
    def regions(self) -> list:
        """Current per-leaf page regions (fetched once per decode step;
        RDMA migrations land between steps via region rebinds)."""
        return [self.pd.mr_array(mr) for mr in self.mrs]

    def rebind(self, new_regions) -> None:
        for mr, r in zip(self.mrs, new_regions):
            self.pd.engine.regions[mr.name] = r

    def close(self) -> None:
        for mr in self.mrs:
            self.pd.dereg_mr(mr)
        self.mrs = []
        self._free = []


def make_paged_step(model, pool: PagePool):
    """The paged decode step, jitted ONCE: page-table gather -> dense
    layout -> ``model.decode_step`` -> scatter updated pages back. Pure
    traced array code (lint_hot_path-clean); regions ride as arguments
    so RDMA-landed pages are visible on the next call."""
    treedef = pool.treedef
    ppslot = pool.pages_per_slot
    page_tokens = pool.page_tokens

    def step(params, tokens, table, pos, regions):
        B = table.shape[0]
        flat = table.reshape(-1)
        dense = []
        for pg in regions:
            rows = pg[flat]                   # (B*ppslot, L, pt, *feat)
            L = pg.shape[1]
            r = rows.reshape((B, ppslot) + rows.shape[1:])
            r = jnp.moveaxis(r, 2, 0)         # (L, B, ppslot, pt, *feat)
            dense.append(r.reshape((L, B, ppslot * page_tokens)
                                   + pg.shape[3:]))
        caches = jax.tree.unflatten(treedef, dense)
        logits, new = model.decode_step(params, tokens, caches, pos)
        outs = []
        for pg, leaf in zip(regions, jax.tree.leaves(new)):
            L = pg.shape[1]
            r = leaf.reshape((L, B, ppslot, page_tokens) + pg.shape[3:])
            r = jnp.moveaxis(r, 0, 2)         # (B, ppslot, L, pt, *feat)
            outs.append(pg.at[flat].set(
                r.reshape((B * ppslot,) + pg.shape[1:])))
        return logits, outs

    # compiles as `jit_step`: the benchmark's decode readers find the
    # paged step's device time under that program name
    return jax.jit(step)
