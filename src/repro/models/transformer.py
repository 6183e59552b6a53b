"""Decoder-only LM assembly: layer plan -> scan groups -> step functions.

Layers are grouped into *scan groups* of identical superblocks (e.g.
recurrentgemma's (rec, rec, attn) pattern scans 8 superblocks; deepseek
scans a group of 3 dense-FFN layers then a group of 58 MoE layers) so HLO
size — and dry-run compile time — is independent of depth.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from repro.models import ffn, mla, moe, rglru, ssm
from repro.models.attention import chunked_attention
from repro.models.layers import (embed, embedding_spec, proj_spec, rmsnorm,
                                 rmsnorm_spec, softcap, unembed, apply_rope)
from repro.models.module import (Spec, abstract_params, init_params,
                                 stack_specs, tree_map_specs)
from repro.parallel import collectives, sharding


# --------------------------------------------------------------------------
# Layer plan
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class LayerKind:
    mix: str          # attn | attn_win | mla | rec | ssm
    ffn: str          # dense | dense_big | moe | none

    @property
    def key(self):
        return (self.mix, self.ffn)


def layer_plan(cfg) -> list[LayerKind]:
    L = cfg.n_layers
    if cfg.family == "ssm":
        return [LayerKind("ssm", "none")] * L
    if cfg.hybrid is not None:
        p = cfg.hybrid.pattern
        kinds = {"rec": LayerKind("rec", "dense"),
                 "attn": LayerKind("attn_win", "dense")}
        return [kinds[p[i % len(p)]] for i in range(L)]
    mix = "mla" if cfg.use_mla else "attn"
    if cfg.moe is not None:
        plan = []
        for i in range(L):
            f = "dense_big" if i < cfg.moe.first_dense else "moe"
            plan.append(LayerKind(mix, f))
        return plan
    return [LayerKind(mix, "dense")] * L


def group_plan(cfg) -> list[tuple[tuple[LayerKind, ...], int]]:
    plan = layer_plan(cfg)
    if cfg.hybrid is not None:
        p = len(cfg.hybrid.pattern)
        n_super, rem = divmod(len(plan), p)
        groups = []
        if n_super:
            groups.append((tuple(plan[:p]), n_super))
        i = n_super * p
        while i < len(plan):                      # group the ragged tail
            j = i
            while j < len(plan) and plan[j] == plan[i]:
                j += 1
            groups.append(((plan[i],), j - i))
            i = j
        return groups
    groups = []
    i = 0
    while i < len(plan):
        j = i
        while j < len(plan) and plan[j] == plan[i]:
            j += 1
        groups.append(((plan[i],), j - i))
        i = j
    return groups


# --------------------------------------------------------------------------
# GQA attention block
# --------------------------------------------------------------------------
def attn_spec(cfg) -> dict:
    D, H, KVH = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    bd = (1, 2) if cfg.qkv_bias else None
    # explicit fan-in scales: the default (shape[-2]) would read heads /
    # head_dim as the fan-in, and a random model drawn that way has
    # near-one-hot attention whose argmax flips under bf16 rounding
    s_in, s_out = D ** -0.5, (H * hd) ** -0.5
    return {
        "wq": proj_spec((D, H, hd), ("embed", "heads", "head_dim"),
                        bias_dims=bd, scale=s_in),
        "wk": proj_spec((D, KVH, hd), ("embed", "kv_heads", "head_dim"),
                        bias_dims=bd, scale=s_in),
        "wv": proj_spec((D, KVH, hd), ("embed", "kv_heads", "head_dim"),
                        bias_dims=bd, scale=s_in),
        "wo": proj_spec((H, hd, D), ("heads", "head_dim", "embed"),
                        scale=s_out),
    }


def _qkv(params, x, positions, cfg):
    def p(w, name):
        y = jnp.einsum("bsd,dhk->bshk", x, w["w"])
        if "b" in w:
            y = y + w["b"].astype(y.dtype)
        return y

    q = p(params["wq"], "q")
    k = p(params["wk"], "k")
    v = p(params["wv"], "v")
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply_sp(params, x, positions, cfg, *, q_chunk, kv_chunk,
                  block_skip, mode):
    """Megatron-SP attention for head-TP archs: ONE shard_map — bf16
    all_gather of the seq-sharded residual in, head-local projections +
    streaming attention, partial out-proj, psum_scatter back to the
    seq-sharded stream. Replaces the auto-partitioner's AG/AR/a2a chaos in
    the projection backward (EXPERIMENTS.md §Perf iter 4)."""
    from jax.sharding import PartitionSpec as P
    ctx = sharding.current()
    mesh = ctx.mesh
    M = mesh.shape["model"]
    B, S, D = x.shape
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    G = H // KVH
    hd = cfg.resolved_head_dim
    H_loc = H // M
    kv_w = max(1, H_loc // G)            # local kv heads touched
    b = sharding.batch_axes_prefix(B) or None
    xspec = P(b, "model", None)
    pspec = P(b, "model")
    wq_spec = sharding.resolve_spec(("embed", "heads", "head_dim"),
                                    params["wq"]["w"].shape, "param")
    wk_spec = sharding.resolve_spec(("embed", "kv_heads", "head_dim"),
                                    params["wk"]["w"].shape, "param")
    wo_spec = sharding.resolve_spec(("heads", "head_dim", "embed"),
                                    params["wo"]["w"].shape, "param")
    kv_sharded = wk_spec[1] is not None  # KVH % M == 0

    def degather(w, axes):
        spec = sharding.resolve_spec(axes, w.shape, "param")
        for d, ent in enumerate(spec):
            if ent is None:
                continue
            for ax in ((ent,) if isinstance(ent, str) else ent):
                if ax != "model":
                    w = jax.lax.all_gather(w, ax, axis=d, tiled=True)
        return w

    def inner(x_l, pos_l, wq, wk, wv, wo):
        wq = degather(wq, ("embed", "heads", "head_dim"))
        wk = degather(wk, ("embed", "kv_heads", "head_dim"))
        wv = degather(wv, ("embed", "kv_heads", "head_dim"))
        wo = degather(wo, ("heads", "head_dim", "embed"))
        x_f = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        pos_f = jax.lax.all_gather(pos_l, "model", axis=1, tiled=True)
        q = jnp.einsum("bsd,dhk->bshk", x_f, wq)          # (B,S,H_loc,hd)
        k = jnp.einsum("bsd,dhk->bshk", x_f, wk)          # local or full KVH
        v = jnp.einsum("bsd,dhk->bshk", x_f, wv)
        if cfg.rope_theta:
            q = apply_rope(q, pos_f, cfg.rope_theta)
            k = apply_rope(k, pos_f, cfg.rope_theta)
        Bl, Sf = q.shape[0], q.shape[1]
        if kv_sharded:
            kvh_loc = KVH // M
            qg = q.reshape(Bl, Sf, kvh_loc, H_loc // kvh_loc, hd)
            out = chunked_attention(qg, k, v, causal=True, q_chunk=q_chunk,
                                    kv_chunk=kv_chunk, block_skip=block_skip)
            out = out.reshape(Bl, Sf, H_loc, hd)
        else:
            # KVH not divisible: wk is replicated; slice the kv heads this
            # rank's q heads group into
            i = jax.lax.axis_index("model")
            start = (i * H_loc) // G
            k_l = jax.lax.dynamic_slice_in_dim(k, start, kv_w, axis=2)
            v_l = jax.lax.dynamic_slice_in_dim(v, start, kv_w, axis=2)
            qg = q.reshape(Bl, Sf, kv_w, H_loc // kv_w, hd)
            out = chunked_attention(qg, k_l, v_l, causal=True,
                                    q_chunk=q_chunk, kv_chunk=kv_chunk,
                                    block_skip=block_skip)
            out = out.reshape(Bl, Sf, H_loc, hd)
        y = jnp.einsum("bshk,hkd->bsd", out, wo)          # partial over heads
        return jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                    tiled=True)

    f = shard_map(inner, mesh=mesh,
                  in_specs=(xspec, pspec, wq_spec, wk_spec, wk_spec,
                            wo_spec),
                  out_specs=xspec, check_vma=False)
    y = f(x, positions, params["wq"]["w"], params["wk"]["w"],
          params["wv"]["w"], params["wo"]["w"])
    return y, None


def attn_apply(params, x, positions, cfg, *, window=0, mode="train",
               cache=None, pos=None, q_chunk=None, kv_chunk=None,
               block_skip=None):
    from repro.perf import FLAGS
    q_chunk = FLAGS.q_chunk if q_chunk is None else q_chunk
    kv_chunk = FLAGS.kv_chunk if kv_chunk is None else kv_chunk
    block_skip = FLAGS.block_skip if block_skip is None else block_skip
    B, S, D = x.shape
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    G = H // KVH
    hd = cfg.resolved_head_dim
    M = sharding.mesh_axis_size("model")
    H_loc = max(1, H // M)
    grouping_ok = (H_loc % G == 0) or (G % H_loc == 0)
    if (mode == "train" and not window and use_sp(cfg, S) and H % M == 0
            and not cfg.qkv_bias and grouping_ok):
        return attn_apply_sp(params, x, positions, cfg, q_chunk=q_chunk,
                             kv_chunk=kv_chunk, block_skip=block_skip,
                             mode=mode)
    q, k, v = _qkv(params, x, positions, cfg)

    if mode in ("train", "prefill"):
        qg = q.reshape(B, S, KVH, G, hd)
        out = collectives.attend(qg, k, v, causal=True, window=window,
                                 q_chunk=q_chunk, kv_chunk=kv_chunk,
                                 block_skip=block_skip)
        y = out.reshape(B, S, H, hd)
        y = jnp.einsum("bshk,hkd->bsd", y, params["wo"]["w"])
        new_cache = None
        if mode == "prefill":
            if window:
                W = min(window, S)
                idxs = S - W + ((jnp.arange(W) - S) % W)
                new_cache = {"k": k[:, idxs], "v": v[:, idxs]}
            else:
                new_cache = {
                    "k": sharding.constrain(k, "batch", "kv_seq", None, None),
                    "v": sharding.constrain(v, "batch", "kv_seq", None, None),
                }
        return y, new_cache

    # decode
    q1 = q[:, 0].reshape(B, KVH, G, hd)
    k1, v1 = k[:, 0], v[:, 0]
    if window:
        out, kc, vc = collectives.window_decode_attention(
            q1, cache["k"], cache["v"], k1, v1, pos, window)
    else:
        out, kc, vc = collectives.seqparallel_decode_attention(
            q1, cache["k"], cache["v"], k1, v1, pos,
            force_local=decode_heads_layout(cfg))
    y = out.reshape(B, 1, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", y, params["wo"]["w"])
    return y, {"k": kc, "v": vc}


def decode_heads_layout(cfg) -> bool:
    """Head-sharded KV cache layout: zero-collective decode attention when
    the kv heads divide the model axis (perf.FLAGS.decode_layout)."""
    from repro.perf import FLAGS
    M = sharding.mesh_axis_size("model")
    return (FLAGS.decode_layout == "heads" and M > 1
            and cfg.n_kv_heads % M == 0)


def attn_cache_spec(cfg, batch: int, seq_len: int, *, window=0) -> dict:
    KVH, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if window:
        W = min(window, seq_len)
        return {"k": Spec((batch, W, KVH, hd),
                          ("batch", "window", "kv_heads", "head_dim"),
                          init="zeros"),
                "v": Spec((batch, W, KVH, hd),
                          ("batch", "window", "kv_heads", "head_dim"),
                          init="zeros")}
    seq_ax = "seq" if decode_heads_layout(cfg) else "kv_seq"
    return {"k": Spec((batch, seq_len, KVH, hd),
                      ("batch", seq_ax, "kv_heads", "head_dim"),
                      init="zeros"),
            "v": Spec((batch, seq_len, KVH, hd),
                      ("batch", seq_ax, "kv_heads", "head_dim"),
                      init="zeros")}


# --------------------------------------------------------------------------
# Block = mixer + FFN
# --------------------------------------------------------------------------
def block_spec(cfg, kind: LayerKind) -> dict:
    D = cfg.d_model
    s: dict = {"ln1": rmsnorm_spec(D)}
    if kind.mix in ("attn", "attn_win"):
        s["attn"] = attn_spec(cfg)
    elif kind.mix == "mla":
        s["mla"] = mla.mla_spec(cfg)
    elif kind.mix == "rec":
        s["rec"] = rglru.rglru_block_spec(cfg)
    elif kind.mix == "ssm":
        s["ssm"] = ssm.mamba2_spec(cfg)
    if kind.ffn == "dense":
        s["ln2"] = rmsnorm_spec(D)
        s["ffn"] = ffn.ffn_spec(D, cfg.d_ff, cfg.act)
    elif kind.ffn == "dense_big":
        s["ln2"] = rmsnorm_spec(D)
        s["ffn"] = ffn.ffn_spec(D, cfg.moe.d_ff_dense, cfg.act)
    elif kind.ffn == "moe":
        s["ln2"] = rmsnorm_spec(D)
        s["moe"] = moe.moe_spec(cfg)
    return s


def block_cache_spec(cfg, kind: LayerKind, batch: int, seq_len: int) -> dict:
    if kind.mix == "attn":
        return attn_cache_spec(cfg, batch, seq_len)
    if kind.mix == "attn_win":
        return attn_cache_spec(cfg, batch, seq_len,
                               window=cfg.hybrid.window)
    if kind.mix == "mla":
        return {"ckv": mla.mla_cache_spec(cfg, batch, seq_len)}
    if kind.mix == "rec":
        return rglru.rglru_cache_spec(cfg, batch)
    if kind.mix == "ssm":
        return ssm.mamba2_cache_spec(cfg, batch)
    raise ValueError(kind)


def use_sp(cfg, S: int) -> bool:
    """Megatron-SP residual applies: perf flag on, divisible seq, and an
    arch family whose blocks tolerate a sequence-sharded stream."""
    from repro.perf import FLAGS
    M = sharding.mesh_axis_size("model")
    return (FLAGS.seq_parallel and M > 1 and S % M == 0
            and cfg.family not in ("ssm", "hybrid", "encdec"))


def block_apply(params, x, positions, cfg, kind: LayerKind, *, mode="train",
                cache=None, pos=None):
    """Returns (x, aux, new_cache)."""
    zc = cfg.zero_centered_norm
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(params["ln1"], x, eps, zero_centered=zc)
    new_cache = None

    if kind.mix in ("attn", "attn_win"):
        window = cfg.hybrid.window if kind.mix == "attn_win" else 0
        a, new_cache = attn_apply(params["attn"], h, positions, cfg,
                                  window=window, mode=mode, cache=cache,
                                  pos=pos)
    elif kind.mix == "mla":
        if mode == "decode":
            a, ckv = mla.mla_decode(params["mla"], h, cache["ckv"], pos, cfg)
            new_cache = {"ckv": ckv}
        elif mode == "prefill":
            a, ckv = mla.mla_forward(params["mla"], h, positions, cfg,
                                     return_cache=True)
            new_cache = {"ckv": ckv}
        elif (use_sp(cfg, x.shape[1]) and cfg.mla.q_lora_rank
              and cfg.n_heads % sharding.mesh_axis_size("model") == 0):
            a = mla.mla_forward_sp(params["mla"], h, positions, cfg)
        else:
            a = mla.mla_forward(params["mla"], h, positions, cfg)
    elif kind.mix == "rec":
        if mode == "decode":
            a, new_cache = rglru.rglru_decode(params["rec"], h, cache, cfg)
        elif mode == "prefill":
            a, new_cache = rglru.rglru_forward(params["rec"], h, cfg,
                                               return_cache=True)
        else:
            a = rglru.rglru_forward(params["rec"], h, cfg)
    elif kind.mix == "ssm":
        if mode == "decode":
            a, new_cache = ssm.mamba2_decode(params["ssm"], h, cache, cfg)
        elif mode == "prefill":
            a, new_cache = ssm.mamba2_forward(params["ssm"], h, cfg,
                                              return_cache=True)
        else:
            a = ssm.mamba2_forward(params["ssm"], h, cfg)
    else:
        raise ValueError(kind)
    x = x + a

    if kind.ffn in ("dense", "dense_big"):
        h = rmsnorm(params["ln2"], x, eps, zero_centered=zc)
        d_ff = params["ffn"]["up"]["w"].shape[-1]
        M = sharding.mesh_axis_size("model")
        sp = (mode != "decode" and use_sp(cfg, x.shape[1])
              and d_ff % M == 0 and "b" not in params["ffn"]["up"])
        x = x + ffn.ffn_apply(params["ffn"], h, cfg.act, sp=sp)
    elif kind.ffn == "moe":
        h = rmsnorm(params["ln2"], x, eps, zero_centered=zc)
        M = sharding.mesh_axis_size("model")
        sp = (mode != "decode" and use_sp(cfg, x.shape[1])
              and cfg.moe.n_shared * cfg.moe.d_ff_shared % max(M, 1) == 0)
        y, aux_moe = moe.moe_apply(params["moe"], h, cfg, sp=sp)
        aux = aux + aux_moe
        x = x + y
    return x, aux, new_cache


def superblock_apply(params, x, positions, cfg, subplan, *, mode="train",
                     cache=None, pos=None):
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    for i, kind in enumerate(subplan):
        key = f"b{i}"
        c = cache[key] if cache is not None else None
        x, a, nc = block_apply(params[key], x, positions, cfg, kind,
                               mode=mode, cache=c, pos=pos)
        aux = aux + a
        new_cache[key] = nc if nc is not None else {}
    return x, aux, new_cache


# --------------------------------------------------------------------------
# The model
# --------------------------------------------------------------------------
class DecoderLM:
    def __init__(self, cfg):
        self.cfg = cfg
        self.groups = group_plan(cfg)

    # -- specs ------------------------------------------------------------
    def param_specs(self) -> dict:
        cfg = self.cfg
        s: dict = {"embed": embedding_spec(cfg.vocab_size, cfg.d_model),
                   "final_norm": rmsnorm_spec(cfg.d_model),
                   "groups": []}
        for subplan, count in self.groups:
            g = {f"b{i}": block_spec(cfg, k) for i, k in enumerate(subplan)}
            s["groups"].append(stack_specs(g, count))
        if not cfg.tie_embeddings:
            s["out_embed"] = embedding_spec(cfg.vocab_size, cfg.d_model)
        if cfg.mtp_depth:
            kind = layer_plan(cfg)[-1]
            s["mtp"] = {
                "proj": Spec((2 * cfg.d_model, cfg.d_model),
                             (None, "embed")),
                "norm_h": rmsnorm_spec(cfg.d_model),
                "norm_e": rmsnorm_spec(cfg.d_model),
                "block": block_spec(cfg, kind),
            }
        return s

    def cache_specs(self, batch: int, seq_len: int) -> list:
        cfg = self.cfg
        out = []
        for subplan, count in self.groups:
            g = {f"b{i}": block_cache_spec(cfg, k, batch, seq_len)
                 for i, k in enumerate(subplan)}
            out.append(stack_specs(g, count))
        return out

    def init(self, key, dtype=None):
        return init_params(self.param_specs(), key, dtype or self.cfg.dtype)

    def init_cache(self, batch: int, seq_len: int):
        return init_params(self.cache_specs(batch, seq_len),
                           jax.random.PRNGKey(0), self.cfg.dtype)

    # -- shared trunk ------------------------------------------------------
    def _residual_constrain(self, x):
        """Megatron-SP: keep the residual stream sequence-sharded over
        `model` (perf.FLAGS.seq_parallel) so CP-attention / SP-MoE regions
        never flap layouts."""
        if use_sp(self.cfg, x.shape[1]):
            return sharding.constrain(x, "batch", "kv_seq", None)
        return sharding.constrain(x, "batch", "seq", "embed")

    def _embed_in(self, params, tokens, embeddings=None):
        cfg = self.cfg
        x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
        if cfg.scale_embeddings:
            x = x * math.sqrt(cfg.d_model)
        if cfg.frontend.kind != "none" and embeddings is not None:
            n = embeddings.shape[1]
            x = jnp.concatenate([embeddings.astype(x.dtype), x[:, n:]],
                                axis=1)
        return self._residual_constrain(x)

    def _run_groups(self, params, x, positions, *, mode, caches=None,
                    pos=None):
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        new_caches = []
        for gi, (subplan, count) in enumerate(self.groups):
            gp = params["groups"][gi]
            gc = caches[gi] if caches is not None else None

            def apply_fn(p_l, c_l, x, subplan=subplan):
                x, aux, nc = superblock_apply(p_l, x, positions, cfg, subplan,
                                              mode=mode, cache=c_l, pos=pos)
                if mode != "decode":
                    x = self._residual_constrain(x)
                return x, aux, nc

            if cfg.remat and mode == "train":
                from repro.perf import FLAGS
                policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                          if FLAGS.remat_policy == "dots"
                          else jax.checkpoint_policies.nothing_saveable)
                apply_fn = jax.checkpoint(apply_fn, policy=policy)

            if cfg.scan_layers and count > 1:
                def body(carry, xs, fn=apply_fn):
                    xc, aux = carry
                    p_l, c_l = xs
                    xc, a, nc = fn(p_l, c_l, xc)
                    return (xc, aux + a), nc

                gc_xs = gc if gc is not None else _empty_stack(subplan)
                (x, aux_total), ncs = lax.scan(body, (x, aux_total),
                                               (gp, gc_xs))
                new_caches.append(ncs)
            else:
                ncs = []
                for li in range(count):
                    p_l = jax.tree.map(lambda a, li=li: a[li], gp)
                    c_l = (jax.tree.map(lambda a, li=li: a[li], gc)
                           if gc is not None else None)
                    x, a, nc = apply_fn(p_l, c_l, x)
                    aux_total = aux_total + a
                    ncs.append(nc)
                if ncs and jax.tree.leaves(ncs[0]):
                    new_caches.append(jax.tree.map(
                        lambda *xs: jnp.stack(xs), *ncs))
                else:
                    new_caches.append(_empty_stack(subplan))
        return x, aux_total, new_caches

    # -- public step functions ---------------------------------------------
    def forward(self, params, tokens, *, embeddings=None):
        """Full-sequence logits (training). Returns (logits, aux)."""
        cfg = self.cfg
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._embed_in(params, tokens, embeddings)
        x, aux, _ = self._run_groups(params, x, positions, mode="train")
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps,
                    zero_centered=cfg.zero_centered_norm)
        table = params["embed"] if cfg.tie_embeddings else params["out_embed"]
        logits = unembed(table, h)
        logits = softcap(logits, cfg.logit_softcap)
        logits = sharding.constrain(logits, "batch", "seq", "vocab")
        extras = {"moe_aux": aux}
        if cfg.mtp_depth:
            extras["mtp_logits"] = self._mtp(params, x, tokens, positions)
        return logits, extras

    def _mtp(self, params, h, tokens, positions):
        """DeepSeek-style 1-depth multi-token prediction head (train)."""
        cfg = self.cfg
        mp = params["mtp"]
        emb_next = embed(params["embed"], tokens[:, 1:]).astype(h.dtype)
        hh = rmsnorm(mp["norm_h"], h[:, :-1], cfg.norm_eps)
        ee = rmsnorm(mp["norm_e"], emb_next, cfg.norm_eps)
        z = jnp.einsum("bsd,dk->bsk", jnp.concatenate([hh, ee], -1),
                       mp["proj"])
        kind = layer_plan(cfg)[-1]
        z, _, _ = block_apply(mp["block"], z, positions[:, 1:], cfg, kind,
                              mode="train")
        z = rmsnorm(params["final_norm"], z, cfg.norm_eps)
        table = params["embed"] if cfg.tie_embeddings else params["out_embed"]
        return softcap(unembed(table, z), cfg.logit_softcap)

    def prefill(self, params, tokens, *, embeddings=None, last_pos=None):
        """Full-sequence forward that emits the decode cache.

        Returns (last_token_logits (B,1,V), caches). `last_pos` (B,)
        selects which row's logits are "last" — the real prompt end when
        `tokens` is right-padded to a bucketed length. Rows at positions
        <= last_pos never see the pad rows (causal masking adds exact
        zeros for fully-masked chunks), so the selected logits — and the
        cache rows a later decode step attends to — are bit-exact with an
        unpadded prefill."""
        cfg = self.cfg
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._embed_in(params, tokens, embeddings)
        x, _, caches = self._run_groups(params, x, positions, mode="prefill")
        if last_pos is None:
            x_last = x[:, -1:]
        else:
            lp = jnp.asarray(last_pos, jnp.int32).reshape(B, 1)
            x_last = jnp.take_along_axis(x, lp[:, :, None], axis=1)
        h = rmsnorm(params["final_norm"], x_last, cfg.norm_eps,
                    zero_centered=cfg.zero_centered_norm)
        table = params["embed"] if cfg.tie_embeddings else params["out_embed"]
        logits = softcap(unembed(table, h), cfg.logit_softcap)
        return logits, caches

    def decode_step(self, params, tokens, caches, pos):
        """One decode step. tokens: (B,1); pos: scalar int32 (write index).

        Returns (logits (B,1,V), caches)."""
        cfg = self.cfg
        B = tokens.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        positions = jnp.broadcast_to(pos, (B,))[:, None]
        x = self._embed_in(params, tokens)
        x, _, caches = self._run_groups(params, x, positions, mode="decode",
                                        caches=caches, pos=pos)
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps,
                    zero_centered=cfg.zero_centered_norm)
        table = params["embed"] if cfg.tie_embeddings else params["out_embed"]
        logits = softcap(unembed(table, h), cfg.logit_softcap)
        logits = sharding.constrain(logits, "batch", "seq", "vocab")
        return logits, caches


def _empty_stack(subplan):
    return {f"b{i}": {} for i in range(len(subplan))}
