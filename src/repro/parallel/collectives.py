"""Distributed attention strategies + partial-softmax merging.

Strategy auto-selection for full-sequence attention on a `model`-axis of
size M (heads H, kv-heads KVH):

  M == 1                -> local chunked attention
  KVH % M == 0          -> head-TP, grouped KV stays grouped (no comm)
  H % M == 0            -> head-TP with KV repeated to H heads (Megatron
                           style duplication when TP > KVH; no comm)
  otherwise             -> context parallelism: q sharded on sequence,
                           KV all-gathered inside shard_map (phi4 H=24,
                           gemma H=8, whisper H=8, recurrentgemma H=10
                           land here on a model=16 mesh)

Decode always uses **KV-sequence parallelism**: the cache is sharded on the
sequence axis over `model`; each shard produces flash-decode partials
(acc, m, l) merged with an exact rescaled psum. This is the beyond-paper
adaptation of FlexiNS T2 (bounded resident set per shard, unbounded
working set) recorded in DESIGN.md §8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.models.attention import (chunked_attention, decode_partials,
                                    finalize_partials)
from repro.parallel import sharding


# --------------------------------------------------------------------------
# Partial-softmax merge (numerically exact)
# --------------------------------------------------------------------------
def merge_partials(acc, m, l, axis_name: str):
    m_g = lax.pmax(m, axis_name)
    c = jnp.exp(m - m_g)
    l_g = lax.psum(l * c, axis_name)
    acc_g = lax.psum(acc * c[..., None], axis_name)
    return acc_g, l_g


def _batch_spec_entry(bsz: int):
    axes = sharding.batch_axes_prefix(bsz)
    return axes if axes else None


# --------------------------------------------------------------------------
# Full-sequence attention dispatcher
# --------------------------------------------------------------------------
def attend(q, k, v, *, causal=True, window=0, cap=0.0, q_chunk=512,
           kv_chunk=1024, block_skip=False, sm_scale=None):
    """q: (B,S,KVH,G,Dk); k/v: (B,S,KVH,D*) -> (B,S,KVH,G,Dv)."""
    B, S, KVH, G, Dk = q.shape
    H = KVH * G
    M = sharding.mesh_axis_size("model")
    kw = dict(causal=causal, window=window, cap=cap, q_chunk=q_chunk,
              kv_chunk=kv_chunk, block_skip=block_skip, sm_scale=sm_scale)

    if M == 1:
        return chunked_attention(q, k, v, **kw)

    if KVH % M == 0:
        q = sharding.constrain(q, "batch", "seq", "kv_heads", None, None)
        k = sharding.constrain(k, "batch", "seq", "kv_heads", None)
        v = sharding.constrain(v, "batch", "seq", "kv_heads", None)
        return chunked_attention(q, k, v, **kw)

    if H % M == 0:
        # repeat KV to full heads; shard the (flattened) head axis
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        qf = q.reshape(B, S, H, 1, Dk)
        qf = sharding.constrain(qf, "batch", "seq", "heads", None, None)
        k = sharding.constrain(k, "batch", "seq", "heads", None)
        v = sharding.constrain(v, "batch", "seq", "heads", None)
        out = chunked_attention(qf, k, v, **kw)
        return out.reshape(B, S, KVH, G, -1)

    if S % M == 0:
        return _context_parallel_attention(q, k, v, **kw)

    return chunked_attention(q, k, v, **kw)


def _context_parallel_attention(q, k, v, *, causal, window, cap, q_chunk,
                                kv_chunk, block_skip, sm_scale):
    """Queries sharded on sequence over `model`; KV either sharded the same
    way (all-gathered inside, the ring-attention-lite scheme) or replicated
    (cross-attention with a KV length that doesn't divide the mesh)."""
    ctx = sharding.current()
    mesh = ctx.mesh
    B, S, KVH, G, Dk = q.shape
    Sk = k.shape[1]
    M = mesh.shape["model"]
    kv_sharded = (Sk % M == 0) and (Sk == S)
    b = _batch_spec_entry(B)
    qspec = P(b, "model", None, None, None)
    kvspec = P(b, "model" if kv_sharded else None, None, None)

    def inner(q_l, k_l, v_l):
        if kv_sharded:
            k_l = lax.all_gather(k_l, "model", axis=1, tiled=True)
            v_l = lax.all_gather(v_l, "model", axis=1, tiled=True)
        off = lax.axis_index("model") * (S // M)
        return chunked_attention(q_l, k_l, v_l, causal=causal, window=window,
                                 cap=cap, q_chunk=q_chunk, kv_chunk=kv_chunk,
                                 q_offset=off, block_skip=block_skip,
                                 sm_scale=sm_scale)

    f = shard_map(inner, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                  out_specs=qspec, check_vma=False)
    return f(q, k, v)


# --------------------------------------------------------------------------
# Decode: KV-sequence-parallel flash-decode
# --------------------------------------------------------------------------
def seqparallel_decode_attention(q, k_cache, v_cache, k_new, v_new, pos, *,
                                 cap=0.0, sm_scale=None, v_dims=None,
                                 force_local=False):
    """One-token decode against a sequence-sharded KV cache.

    q: (B,KVH,G,Dk); caches: (B,S,KVH,D*); new entries: (B,KVH,D*);
    pos: scalar int32 (index where the new entry is written; attention
    covers positions [0, pos]). Returns (out (B,KVH,G,Dv), k_cache, v_cache).

    v_dims: MLA absorbed mode — V is k_cache[..., :v_dims] (shared latent;
    v_cache/v_new are ignored and returned as None).
    """
    B, S, KVH, Dk = k_cache.shape
    ctx = sharding.current()
    M = sharding.mesh_axis_size("model")
    mla = v_dims is not None
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))

    def _update(cache, new, p, s0):
        """Per-request scatter write at local index p - s0 (rows out of
        this shard's range keep their original content)."""
        idx = p - s0
        in_range = (idx >= 0) & (idx < cache.shape[1])
        safe = jnp.clip(idx, 0, cache.shape[1] - 1)
        upd = cache.at[jnp.arange(cache.shape[0]), safe].set(new)
        return jnp.where(in_range[:, None, None, None], upd, cache)

    if ctx is None or M == 1 or S % M or force_local:
        # force_local: head-sharded cache layout — every einsum below is
        # already local per head shard; no shard_map, no collectives
        k_cache = _update(k_cache, k_new, pos, 0)
        if mla:
            v_cache2 = k_cache[..., :v_dims]
        else:
            v_cache = _update(v_cache, v_new, pos, 0)
            v_cache2 = v_cache
        acc, m, l = decode_partials(q, k_cache, v_cache2, jnp.arange(S), pos,
                                    cap=cap, sm_scale=sm_scale)
        out = finalize_partials(acc, l).astype(q.dtype)
        return out, k_cache, (None if mla else v_cache)

    mesh = ctx.mesh
    b = _batch_spec_entry(B)
    qspec = P(b, None, None, None)
    cspec = P(b, "model", None, None)
    nspec = P(b, None, None)
    pspec = P(b)

    def inner(q_l, kc, vc, kn, vn, p):
        i = lax.axis_index("model")
        S_loc = S // M
        s0 = i * S_loc
        kc = _update(kc, kn, p, s0)
        if mla:
            vc_eff = kc[..., :v_dims]
        else:
            vc = _update(vc, vn, p, s0)
            vc_eff = vc
        kvpos = s0 + jnp.arange(S_loc)
        acc, m, l = decode_partials(q_l, kc, vc_eff, kvpos, p, cap=cap,
                                    sm_scale=sm_scale)
        acc, l = merge_partials(acc, m, l, "model")
        return finalize_partials(acc, l).astype(q_l.dtype), kc, vc

    f = shard_map(inner, mesh=mesh,
                  in_specs=(qspec, cspec, cspec, nspec, nspec, pspec),
                  out_specs=(qspec, cspec, cspec), check_vma=False)
    if mla:
        # pass k_cache twice (second is ignored structurally but keeps the
        # shard_map signature uniform); drop the dummy on return
        out, k_cache, _ = f(q, k_cache, k_cache, k_new, k_new, pos)
        return out, k_cache, None
    out, k_cache, v_cache = f(q, k_cache, v_cache, k_new, v_new, pos)
    return out, k_cache, v_cache


def window_decode_attention(q, k_win, v_win, k_new, v_new, pos, window: int,
                            *, cap=0.0, sm_scale=None):
    """One-token decode against a rolling window cache (B,W,KVH,D*).
    pos: scalar or (B,) per-request positions."""
    B, W = k_win.shape[0], k_win.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    slot = pos % W
    rows = jnp.arange(B)
    k_win = k_win.at[rows, slot].set(k_new)
    v_win = v_win.at[rows, slot].set(v_new)
    slots = jnp.arange(W)
    token_of_slot = pos[:, None] - ((pos[:, None] - slots[None]) % W)  # (B,W)
    valid = token_of_slot >= 0
    if window < W:
        valid &= token_of_slot > pos[:, None] - window
    acc, m, l = decode_partials(q, k_win, v_win, token_of_slot, pos, cap=cap,
                                extra_mask=valid, sm_scale=sm_scale)
    return finalize_partials(acc, l).astype(q.dtype), k_win, v_win
