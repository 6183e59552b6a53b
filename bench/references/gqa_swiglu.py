"""Plain reference of a decoder-only transformer with grouped-query
attention, RoPE, RMSNorm and a SwiGLU feed-forward block: the
architecture of phi4-mini-3.8b and of InternVL2-2B's language model
(InternLM2-1.8B), as their configuration files state it.

Straight `jax.numpy` in float32 at `Precision.HIGHEST`: no kernels, no
cache, no batching tricks, and nothing imported from the program. It
reads the benchmark's weight tree by name (``bench/weights.py`` makes
it) and runs one layer at a time, each layer's weights cast to float32
inside its own call, so that it fits beside the weights on one chip.

``quant="fp8"`` is the control: every matrix product takes its inputs
rounded to float8 e4m3 (per output channel for weights, per row for
activations, scaled to the format's range), the step below bfloat16
that would tempt a later change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F8_MAX = 448.0


def fake_quant(x, axis, quant):
    """x rounded to float8 e4m3 with a scale per slice along `axis`
    (the contraction axis) — or x itself when `quant` is None."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x: (B, S, heads, hd); rotates the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs    # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def check_config(cfg: dict) -> None:
    if cfg.get("partial_rotary_factor", 1.0) != 1.0 or \
            cfg.get("rope_scaling") is not None or \
            cfg.get("attention_bias") or cfg.get("mlp_bias") or \
            cfg.get("hidden_act") != "silu":
        raise ValueError("this reference covers full-rotary, unscaled "
                         "RoPE, bias-free SwiGLU models only")


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(block, idx, x, *, cfg_items, quant):
    cfg = dict(cfg_items)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def w(*names):
        a = block
        for n in names:
            a = a[n]
        return lax.dynamic_index_in_dim(a, idx, keepdims=False) \
            .astype(jnp.float32)

    def proj(x, wt, spec, w_axis):
        return jnp.einsum(spec, fake_quant(x, -1, quant),
                          fake_quant(wt, w_axis, quant), precision=HI)

    B, S, _ = x.shape
    h = rmsnorm(x, w("ln1", "scale"), eps)
    q = rope(proj(h, w("attn", "wq", "w"), "bsd,dhk->bshk", 0), theta)
    k = rope(proj(h, w("attn", "wk", "w"), "bsd,dhk->bshk", 0), theta)
    v = proj(h, w("attn", "wv", "w"), "bsd,dhk->bshk", 0)
    hd = q.shape[-1]
    q = q.reshape(B, S, KVH, H // KVH, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HI)
    o = o.reshape(B, S, H, hd)
    wo = w("attn", "wo", "w")                               # (H, hd, D)
    x = x + jnp.einsum("bshk,hkd->bsd",
                       fake_quant(o.reshape(B, S, H * hd), -1, quant)
                       .reshape(B, S, H, hd),
                       fake_quant(wo.reshape(H * hd, -1), 0, quant)
                       .reshape(wo.shape), precision=HI)
    h = rmsnorm(x, w("ln2", "scale"), eps)
    g = proj(h, w("ffn", "gate", "w"), "bsd,df->bsf", 0)
    u = proj(h, w("ffn", "up", "w"), "bsd,df->bsf", 0)
    return x + proj(jax.nn.silu(g) * u, w("ffn", "down", "w"),
                    "bsf,fd->bsd", 0)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, rows, final_scale, *, eps):
    return rmsnorm(x.reshape(-1, x.shape[-1])[rows], final_scale, eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _logits(table, h, *, quant):
    return jnp.einsum("nd,vd->nv", fake_quant(h, -1, quant),
                      fake_quant(table.astype(jnp.float32), 1, quant),
                      precision=HI)


def logits_at(weights, cfg: dict, tokens: np.ndarray, rows: np.ndarray,
              quant: str | None = None):
    """Logits (len(rows), vocab) float32 of a full causal forward over
    `tokens` (B, S), at flat row indices `rows` into the (B*S) grid."""
    check_config(cfg)
    groups = weights["groups"]
    if len(groups) != 1 or set(groups[0]) != {"b0"}:
        raise ValueError("expected one stacked group of identical blocks")
    block = groups[0]["b0"]
    L = int(cfg["num_hidden_layers"])
    items = tuple(sorted((k, cfg[k]) for k in (
        "rms_norm_eps", "rope_theta", "num_attention_heads",
        "num_key_value_heads")))
    eps = float(cfg["rms_norm_eps"])
    fscale = weights["final_norm"]["scale"].astype(jnp.float32)
    x = _embed(weights["embed"]["table"], jnp.asarray(tokens))
    for i in range(L):
        x = _layer(block, jnp.int32(i), x, cfg_items=items, quant=quant)
    h = _final(x, jnp.asarray(rows), fscale, eps=eps)
    head = weights["embed" if cfg["tie_word_embeddings"] else "out_embed"]
    return _logits(head["table"], h, quant=quant)
