"""T1 — header-only offloading TX path, on the TPU interconnect.

`transmit` moves a sharded pytree across a mesh axis (pod->pod) with the
payload travelling **exactly once over the fattest direct path**:

  1. stripe: the payload is constrained to shard over every stripe axis
     (packet spraying — each ICI link carries 1/prod(stripe) of the bytes;
     a tensor already produced in that layout moves zero-copy);
  2. wire: one collective_permute along the transfer axis;
  3. optional int8 wire compression (scale per trailing block) — the
     beyond-paper extension of "don't move what you can reconstruct".

`transmit_staged` is the paper's *naive* baseline (Fig. 6a/12): payload is
first gathered into a replicated staging buffer ("Arm memory"), permuted
redundantly, then re-sharded. Same result, ~stripe-factor more wire bytes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core.descriptors import TransferPlan
from repro.models import module as mod
from repro.obs import metrics
from repro.parallel import sharding


def _leaf_spec(spec: mod.Spec) -> P:
    return sharding.resolve_spec(spec.axes, spec.shape, "param")


def _act_leaf_spec(spec: mod.Spec) -> P:
    return sharding.resolve_spec(spec.axes, spec.shape, "act")


def _quantize(x, bits: int):
    assert bits == 8
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _permute_leaf(x, spec: P, axis: str, shift: int):
    ctx = sharding.current()
    mesh = ctx.mesh
    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    def inner(x_l):
        return lax.ppermute(x_l, axis, perm)

    f = shard_map(inner, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    return f(x)


def transmit(tree, spec_tree, plan: TransferPlan):
    """FlexiNS path: stripe + direct ppermute (+ optional int8 wire)."""
    # resolved at call time so per-bench-module registry swaps see it
    metrics.get_registry().scope("tx_engine").counter("transmits").inc()
    ctx = sharding.current()
    if ctx is None or plan.axis not in ctx.mesh.axis_names:
        return tree     # single-device / no pod axis: transfer is identity

    def one(x, s: mod.Spec):
        spec = _act_leaf_spec(s)
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(ctx.mesh, spec))
        if plan.quantize_bits:
            q, scale = _quantize(x, plan.quantize_bits)
            q = _permute_leaf(q, spec, plan.axis, plan.shift)
            scale = _permute_leaf(scale, spec, plan.axis, plan.shift)
            return _dequantize(q, scale, x.dtype)
        return _permute_leaf(x, spec, plan.axis, plan.shift)

    return jax.tree.map(one, tree, spec_tree,
                        is_leaf=lambda v: isinstance(v, jnp.ndarray)
                        or hasattr(v, "shape"))


def transmit_staged(tree, spec_tree, plan: TransferPlan):
    """Naive baseline: payload staged through a replicated buffer before
    the wire (the 'through Arm memory' path, paper Fig. 6a)."""
    metrics.get_registry().scope("tx_engine") \
        .counter("staged_transmits").inc()
    ctx = sharding.current()
    if ctx is None or plan.axis not in ctx.mesh.axis_names:
        return tree

    mesh = ctx.mesh
    batch_only = ctx.act_rules.get("batch")

    def one(x, s: mod.Spec):
        # stage: replicate over every axis except the batch axes
        spec_r = sharding.resolve_spec(
            tuple("batch" if a == "batch" else None for a in s.axes),
            s.shape, "act")
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec_r))
        x = _permute_leaf(x, spec_r, plan.axis, plan.shift)
        # land back in the streaming layout
        spec = _act_leaf_spec(s)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))

    return jax.tree.map(one, tree, spec_tree,
                        is_leaf=lambda v: hasattr(v, "shape"))
