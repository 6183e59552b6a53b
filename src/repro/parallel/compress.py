"""Compressed cross-replica reduction (int8 on the wire).

`compressed_psum_mean` implements reduce-scatter + all-gather with int8
payloads and per-block f32 scales: each rank quantizes its shard-chunks,
all_to_all's them (the RS half), dequant-accumulates locally in f32,
re-quantizes the partial sums and all-gathers (the AG half). Wire bytes
are ~4x less than an f32 ring all-reduce (~2x less than bf16).

Deployment note (DESIGN.md §4): inside the jit-SPMD training step XLA owns
the gradient cross-replica-sum, so this utility applies to *explicit*
reduction paths — the KV-transfer wire (TransferPlan.quantize_bits), the
offload-engine response path, and shard_map-structured training loops.
Error feedback (residual carrying) is the caller's choice: the function
returns the quantization residual so callers can fold it into the next
step's input.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax



def _quant(x, axis=-1):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(x, axis_name: str, *, return_residual: bool = False):
    """Mean over `axis_name` with int8 wire traffic. Call inside shard_map.

    x: (..., F) f32 with F divisible by the axis size."""
    n = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1)
    F = flat.shape[0]
    assert F % n == 0, (F, n)
    chunks = flat.reshape(n, F // n)

    # RS half: quantize chunks, exchange, dequant-accumulate in f32
    q, s = _quant(chunks)
    q = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=True)
    s = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=True)
    part = _dequant(q.reshape(n, F // n), s.reshape(n, 1)).sum(0) / n

    # AG half: quantize the reduced shard, gather all shards
    q2, s2 = _quant(part[None])
    q2 = lax.all_gather(q2, axis_name, axis=0, tiled=True)
    s2 = lax.all_gather(s2, axis_name, axis=0, tiled=True)
    out = _dequant(q2, s2).reshape(-1).reshape(x.shape)
    if not return_residual:
        return out
    exact = lax.pmean(x, axis_name)
    return out, exact - out


def wire_bytes_ratio(dtype_bytes: int = 4) -> float:
    """Wire savings vs a same-shape ring all-reduce of `dtype_bytes`."""
    return dtype_bytes / 1.0   # int8 payload; scales are negligible
