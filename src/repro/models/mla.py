"""Multi-head Latent Attention (DeepSeek-V2/V3). [arXiv:2412.19437]

Train/prefill run the *expanded* form (latent up-projected to per-head K/V,
flash-style chunked attention over qk_dim = nope+rope). Decode runs the
*absorbed* form: queries are pulled into latent space through W_UK and
attention runs against the cached 576-byte-per-token latent — the extreme
case of the FlexiNS insight "never move (or store) what you can
reconstruct": the KV-transfer payload for MLA is the latent, 10-60x smaller
than expanded KV.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.models.layers import apply_rope, rmsnorm, rmsnorm_spec
from repro.models.module import Spec
from repro.parallel import collectives, sharding


def latent_dim(cfg) -> int:
    a = cfg.mla
    return a.kv_lora_rank + a.qk_rope_head_dim


def mla_spec(cfg) -> dict:
    a = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    s: dict = {}
    if a.q_lora_rank:
        s["w_dq"] = Spec((D, a.q_lora_rank), ("embed", "q_lora"))
        s["q_norm"] = rmsnorm_spec(a.q_lora_rank)
        s["w_uq"] = Spec((a.q_lora_rank, H, qk), ("q_lora", "heads", "head_dim"))
    else:
        s["w_q"] = Spec((D, H, qk), ("embed", "heads", "head_dim"))
    s["w_dkv"] = Spec((D, a.kv_lora_rank), ("embed", "kv_lora"))
    s["kv_norm"] = rmsnorm_spec(a.kv_lora_rank)
    s["w_kr"] = Spec((D, a.qk_rope_head_dim), ("embed", None))
    s["w_uk"] = Spec((a.kv_lora_rank, H, a.qk_nope_head_dim),
                     ("kv_lora", "heads", "head_dim"))
    s["w_uv"] = Spec((a.kv_lora_rank, H, a.v_head_dim),
                     ("kv_lora", "heads", "head_dim"))
    s["w_o"] = Spec((H, a.v_head_dim, D), ("heads", "head_dim", "embed"))
    return s


def _queries(params, x, positions, cfg):
    a = cfg.mla
    if a.q_lora_rank:
        ql = rmsnorm(params["q_norm"],
                     jnp.einsum("bsd,dr->bsr", x, params["w_dq"]),
                     cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", ql, params["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"])
    qn = q[..., :a.qk_nope_head_dim]
    qr = apply_rope(q[..., a.qk_nope_head_dim:], positions, cfg.rope_theta)
    return qn, qr


def _latent(params, x, positions, cfg):
    ckv = rmsnorm(params["kv_norm"],
                  jnp.einsum("bsd,dr->bsr", x, params["w_dkv"]), cfg.norm_eps)
    kr = apply_rope(jnp.einsum("bsd,dr->bsr", x, params["w_kr"]),
                    positions, cfg.rope_theta)
    return ckv, kr


def mla_forward_sp(params, x, positions, cfg, *, q_chunk=512, kv_chunk=1024):
    """Megatron-SP MLA: the residual stream stays sequence-sharded; only
    the LATENTS (q_lora + kv_lora + rope ~ 2176 B/token, vs 14 KiB/token of
    residual) are all-gathered inside one shard_map; heads are local; the
    out-projection psum_scatters back to the seq-sharded stream. The paper's
    'move the compressed representation, reconstruct at the consumer'
    insight applied to the training plane (EXPERIMENTS.md §Perf iter 6)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.models.attention import chunked_attention

    a = cfg.mla
    ctx = sharding.current()
    mesh = ctx.mesh
    M = mesh.shape["model"]
    B, S, D = x.shape
    H = cfg.n_heads
    H_loc = H // M

    # latents: pointwise over seq -> computed on the local shard, no comm
    assert a.q_lora_rank, "SP path assumes q-lora (deepseek-v3 config)"
    ql = rmsnorm(params["q_norm"],
                 jnp.einsum("bsd,dr->bsr", x, params["w_dq"]), cfg.norm_eps)
    ckv = rmsnorm(params["kv_norm"],
                  jnp.einsum("bsd,dr->bsr", x, params["w_dkv"]), cfg.norm_eps)
    kr = apply_rope(jnp.einsum("bsd,dr->bsr", x, params["w_kr"]),
                    positions, cfg.rope_theta)

    b = sharding.batch_axes_prefix(B) or None
    lspec = P(b, "model", None)
    pspec = P(b, "model")
    huq = sharding.resolve_spec(("q_lora", "heads", "head_dim"),
                                params["w_uq"].shape, "param")
    huk = sharding.resolve_spec(("kv_lora", "heads", "head_dim"),
                                params["w_uk"].shape, "param")
    huv = sharding.resolve_spec(("kv_lora", "heads", "head_dim"),
                                params["w_uv"].shape, "param")
    hwo = sharding.resolve_spec(("heads", "head_dim", "embed"),
                                params["w_o"].shape, "param")

    def degather(w, axes):
        spec = sharding.resolve_spec(axes, w.shape, "param")
        for d, ent in enumerate(spec):
            if ent is None:
                continue
            for ax in ((ent,) if isinstance(ent, str) else ent):
                if ax != "model":
                    w = lax.all_gather(w, ax, axis=d, tiled=True)
        return w

    def inner(ql_l, ckv_l, kr_l, pos_l, w_uq, w_uk, w_uv, w_o):
        w_uq = degather(w_uq, ("q_lora", "heads", "head_dim"))
        w_uk = degather(w_uk, ("kv_lora", "heads", "head_dim"))
        w_uv = degather(w_uv, ("kv_lora", "heads", "head_dim"))
        w_o = degather(w_o, ("heads", "head_dim", "embed"))
        ql_f = lax.all_gather(ql_l, "model", axis=1, tiled=True)
        ckv_f = lax.all_gather(ckv_l, "model", axis=1, tiled=True)
        kr_f = lax.all_gather(kr_l, "model", axis=1, tiled=True)
        pos_f = lax.all_gather(pos_l, "model", axis=1, tiled=True)
        q = jnp.einsum("bsr,rhk->bshk", ql_f, w_uq)      # (B,S,H_loc,qk)
        qn = q[..., :a.qk_nope_head_dim]
        qr = apply_rope(q[..., a.qk_nope_head_dim:], pos_f, cfg.rope_theta)
        kn = jnp.einsum("bsr,rhk->bshk", ckv_f, w_uk)
        v = jnp.einsum("bsr,rhv->bshv", ckv_f, w_uv)
        Bl, Sf = q.shape[0], q.shape[1]
        qq = jnp.concatenate([qn, qr], axis=-1)
        kk = jnp.concatenate(
            [kn, jnp.broadcast_to(kr_f[:, :, None],
                                  (Bl, Sf, H_loc, a.qk_rope_head_dim))], -1)
        out = chunked_attention(qq.reshape(Bl, Sf, H_loc, 1, -1), kk, v,
                                causal=True, q_chunk=q_chunk,
                                kv_chunk=kv_chunk)
        out = out.reshape(Bl, Sf, H_loc, a.v_head_dim)
        y = jnp.einsum("bshv,hvd->bsd", out, w_o).astype(ql_l.dtype)
        return lax.psum_scatter(y, "model", scatter_dimension=1, tiled=True)

    f = shard_map(inner, mesh=mesh,
                  in_specs=(lspec, lspec, lspec, pspec, huq, huk, huv,
                            hwo),
                  out_specs=lspec, check_vma=False)
    return f(ql, ckv, kr, positions, params["w_uq"], params["w_uk"],
             params["w_uv"], params["w_o"])


def mla_forward(params, x, positions, cfg, *, return_cache: bool = False,
                q_chunk=512, kv_chunk=1024):
    """Expanded-form MLA over a full sequence. x: (B,S,D)."""
    a = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    qn, qr = _queries(params, x, positions, cfg)
    ckv, kr = _latent(params, x, positions, cfg)

    kn = jnp.einsum("bsr,rhk->bshk", ckv, params["w_uk"])
    v = jnp.einsum("bsr,rhv->bshv", ckv, params["w_uv"])
    q = jnp.concatenate([qn, qr], axis=-1)                     # (B,S,H,qk)
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, :, None], (B, S, H, a.qk_rope_head_dim))],
        axis=-1)
    out = collectives.attend(q.reshape(B, S, H, 1, -1), k, v, causal=True,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = out.reshape(B, S, H, a.v_head_dim)
    y = jnp.einsum("bshv,hvd->bsd", out, params["w_o"])
    if not return_cache:
        return y
    cache = jnp.concatenate([ckv, kr], axis=-1)[:, :, None, :]  # (B,S,1,C)
    cache = sharding.constrain(cache, "batch", "kv_seq", None, None)
    return y, cache


def mla_decode(params, x, cache, pos, cfg):
    """Absorbed-form single-token decode. x: (B,1,D); cache: (B,S,C)."""
    a = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))[:, None]
    qn, qr = _queries(params, x, positions, cfg)               # (B,1,H,*)
    # absorb W_UK: q_eff[h] = qn[h] @ W_UK[:,h,:]^T  -> latent space
    q_eff = jnp.einsum("bhn,rhn->bhr", qn[:, 0], params["w_uk"])
    q_full = jnp.concatenate([q_eff, qr[:, 0]], axis=-1)       # (B,H,C)
    ckv, kr = _latent(params, x, positions, cfg)
    new = jnp.concatenate([ckv, kr], axis=-1)[:, 0]            # (B,C)

    qk_dim = a.qk_nope_head_dim + a.qk_rope_head_dim
    # q grouped as (B, KVH=1, G=H, C): the latent cache is MQA-like
    out, cache, _ = collectives.seqparallel_decode_attention(
        q_full[:, None, :, :], cache, None, new[:, None, :], None, pos,
        sm_scale=1.0 / math.sqrt(qk_dim), v_dims=a.kv_lora_rank)
    # out: (B, KVH=1, G=H, kv_lora)
    out = out[:, 0]                                            # (B,H,latent)
    o = jnp.einsum("bhr,rhv->bhv", out.astype(jnp.float32),
                   params["w_uv"].astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("bhv,hvd->bd", o, params["w_o"])[:, None]
    return y, cache


def mla_cache_spec(cfg, batch: int, seq_len: int) -> Spec:
    return Spec((batch, seq_len, 1, latent_dim(cfg)),
                ("batch", "kv_seq", None, None), init="zeros")
