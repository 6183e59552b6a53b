"""Jit'd wrapper for ring_consume. `interpret=True` runs the Pallas body
on the CPU; only a caller that asks for it gets it."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.ring_pipe.ring_pipe import ring_consume as _kernel
from repro.kernels.ring_pipe import ref


@partial(jax.jit, static_argnames=("interpret",))
def ring_consume(slots, src_idx, *, interpret=False):
    return _kernel(slots, src_idx, interpret=interpret)


reference = ref.reference
