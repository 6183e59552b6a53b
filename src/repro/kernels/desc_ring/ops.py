"""Jit'd wrappers + host boundary for the device-resident ring.

`produce` is ONE donated launch per publish batch (counted as
`fused/ring_launches` in the registry — separate from the per-flush
`fused/launches` scatter/gather contract, so the two gates compose
independently). `consume` is one launch per poll; its full-capacity
scan keys the jit cache on the ring shape alone, so a ring compiles
exactly two programs however ragged the batches.

Slot memory crosses the host/device boundary as int32 PAIRS
(`(capacity, 2*WIDTH) int32`): the host's 64B int64 cachelines byte-view
to pairs on the way in and view back on the way out — bit-exact, and
immune to the x64=off pin silently truncating device int64.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.desc_ring import desc_ring
from repro.obs import metrics


@partial(jax.jit, donate_argnums=(0, 1))
def _produce(slots, flags, batch, head):
    return desc_ring.produce(slots, flags, batch, head)


@jax.jit
def _consume(slots, flags, tail):
    return desc_ring.consume(slots, flags, tail)


@partial(jax.jit, donate_argnums=(0, 1))
def _produce_consume(slots, flags, batch, head, tail):
    return desc_ring.produce_consume(slots, flags, batch, head, tail)


def _count():
    metrics.get_registry().scope("fused").counter("ring_launches").inc()


def alloc(capacity: int, width: int):
    """Device slot memory + valid flags (int32-pair slot rows)."""
    return (jnp.zeros((capacity, 2 * width), jnp.int32),
            jnp.zeros((capacity,), jnp.uint8))


def produce(slots, flags, head: int, batch: np.ndarray):
    """ONE donated launch publishing the host int64 batch block."""
    cap = slots.shape[0]
    b32 = np.ascontiguousarray(batch, np.int64).view(np.int32)
    _count()
    return _produce(slots, flags, b32, head % (2 * cap))


def consume(slots, flags, tail: int, limit: int) -> np.ndarray:
    """One launch scanning the valid prefix; returns up to `limit` rows
    as host int64 descriptors (the int32 pairs view straight back)."""
    cap = slots.shape[0]
    rows, k = _consume(slots, flags, tail % (2 * cap))
    _count()
    k = min(int(k), limit)
    if k == 0:
        return np.empty((0, slots.shape[1] // 2), np.int64)
    return np.ascontiguousarray(np.asarray(rows[:k])).view(np.int64)


def produce_consume(slots, flags, head: int, tail: int,
                    batch: np.ndarray, limit: int):
    """Fused publish+poll: ONE donated launch producing the host int64
    batch AND scanning the valid prefix from tail. Returns (slots',
    flags', up-to-`limit` host int64 rows) — exactly `produce` then
    `consume`, for half the launches (the one-launch serve step)."""
    cap = slots.shape[0]
    b32 = np.ascontiguousarray(batch, np.int64).view(np.int32)
    _count()
    slots, flags, rows, k = _produce_consume(
        slots, flags, b32, head % (2 * cap), tail % (2 * cap))
    k = min(int(k), limit)
    if k == 0:
        return slots, flags, np.empty((0, slots.shape[1] // 2), np.int64)
    return slots, flags, \
        np.ascontiguousarray(np.asarray(rows[:k])).view(np.int64)
