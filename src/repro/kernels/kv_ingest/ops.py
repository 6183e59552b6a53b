"""Jit'd wrapper for the kv_ingest kernel. `interpret=True` runs the
Pallas body on the CPU; only a caller that asks for it gets it."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.kv_ingest.kv_ingest import kv_ingest as _kernel
from repro.kernels.kv_ingest import ref


@partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def kv_ingest(pages, payload, page_ids, *, interpret=False):
    return _kernel(pages, payload, page_ids, interpret=interpret)


reference = ref.reference
