"""Jit'd public wrapper for the Pallas flash-attention kernel, with the
jnp oracle available for verification. `interpret=True` runs the Pallas
body on the CPU; only a caller that asks for it gets it."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention import ref


@partial(jax.jit, static_argnames=("causal", "window", "sm_scale", "cap",
                                   "block_q", "block_k", "interpret"))
def attention(q, k, v, *, causal=True, window=0, sm_scale=None, cap=0.0,
              block_q=128, block_k=128, interpret=False):
    return flash_attention(q, k, v, causal=causal, window=window,
                           sm_scale=sm_scale, cap=cap, block_q=block_q,
                           block_k=block_k, interpret=interpret)


reference = ref.reference
