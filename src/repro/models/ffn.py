"""Dense feed-forward blocks (GLU variants + plain MLP).

`ffn_apply_sp` is the explicit Megatron-SP variant: input arrives
sequence-sharded over `model`; one bf16 all_gather in, one bf16
psum_scatter out — replacing the implicit AG + f32 all-reduce pair the
auto-SPMD path emits (the CPU pipeline lacks the reduce-scatter-creation
pass, so we encode the schedule explicitly; EXPERIMENTS.md §Perf iter 3)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.models.layers import act_fn, linear, linear_spec
from repro.parallel import sharding


def ffn_spec(d_model: int, d_ff: int, act: str, *, bias: bool = False) -> dict:
    if act in ("swiglu", "geglu"):
        return {
            "gate": linear_spec(d_model, d_ff, ("embed", "mlp"), bias=bias),
            "up": linear_spec(d_model, d_ff, ("embed", "mlp"), bias=bias),
            "down": linear_spec(d_ff, d_model, ("mlp", "embed"), bias=bias),
        }
    return {
        "up": linear_spec(d_model, d_ff, ("embed", "mlp"), bias=bias),
        "down": linear_spec(d_ff, d_model, ("mlp", "embed"), bias=bias),
    }


def ffn_apply(params, x, act: str, *, sp: bool = False):
    if sp:
        # pick the cheaper gather: Megatron-SP moves the activations
        # (2 x tokens x D bytes on the wire), the ZeRO-style variant moves
        # the weights once (3 x D x F). Small-F FFNs (shared experts) are
        # far cheaper weight-gathered.
        ctx = sharding.current()
        B, S, D = x.shape
        bs = 1
        for ax in sharding.batch_axes_prefix(B):
            bs *= ctx.mesh.shape[ax]
        F = params["up"]["w"].shape[-1]
        n_mats = 3 if "gate" in params else 2
        act_bytes = 2 * (B // bs) * S * D
        w_bytes = n_mats * D * F
        if w_bytes < act_bytes:
            return _ffn_apply_wg(params, x, act)
        return _ffn_apply_sp(params, x, act)
    f = act_fn(act)
    if "gate" in params:
        h = f(linear(params["gate"], x)) * linear(params["up"], x)
    else:
        h = f(linear(params["up"], x))
    h = sharding.constrain(h, "batch", "seq", "mlp")
    return linear(params["down"], h)


def _gather_all(w, axes):
    """Fully de-shard a weight inside shard_map (incl. the model axis)."""
    spec = sharding.resolve_spec(axes, w.shape, "param")
    for d, ent in enumerate(spec):
        if ent is None:
            continue
        for ax in ((ent,) if isinstance(ent, str) else ent):
            w = lax.all_gather(w, ax, axis=d, tiled=True)
    return w


def _ffn_apply_wg(params, x, act: str):
    """Weight-gathered token-local FFN: x stays sequence-sharded; the
    (small) weights are all-gathered once; zero activation collectives."""
    ctx = sharding.current()
    mesh = ctx.mesh
    B = x.shape[0]
    f = act_fn(act)
    has_gate = "gate" in params
    b = sharding.batch_axes_prefix(B) or None
    xspec = P(b, "model", None)
    gspec = sharding.resolve_spec(("embed", "mlp"), params["up"]["w"].shape,
                                  "param")
    dspec = sharding.resolve_spec(("mlp", "embed"), params["down"]["w"].shape,
                                  "param")

    def inner(x_l, wg, wu, wd):
        wu = _gather_all(wu, ("embed", "mlp"))
        wd = _gather_all(wd, ("mlp", "embed"))
        if has_gate:
            wg = _gather_all(wg, ("embed", "mlp"))
            h = f(jnp.einsum("bsd,df->bsf", x_l, wg)) \
                * jnp.einsum("bsd,df->bsf", x_l, wu)
        else:
            h = f(jnp.einsum("bsd,df->bsf", x_l, wu))
        return jnp.einsum("bsf,fd->bsd", h, wd)

    wg = params["gate"]["w"] if has_gate else params["up"]["w"]
    fsp = shard_map(inner, mesh=mesh,
                    in_specs=(xspec, gspec, gspec, dspec),
                    out_specs=xspec, check_vma=False)
    return fsp(x, wg, params["up"]["w"], params["down"]["w"])


def _gather_w(w, axes):
    """ZeRO-style weight de-shard for every non-model axis, in-shard_map."""
    spec = sharding.resolve_spec(axes, w.shape, "param")
    for d, ent in enumerate(spec):
        if ent is None:
            continue
        for ax in ((ent,) if isinstance(ent, str) else ent):
            if ax != "model":
                w = lax.all_gather(w, ax, axis=d, tiled=True)
    return w


def _ffn_apply_sp(params, x, act: str):
    """x: (B, S, D) sequence-sharded over `model`."""
    ctx = sharding.current()
    mesh = ctx.mesh
    B, S, D = x.shape
    f = act_fn(act)
    has_gate = "gate" in params
    b = sharding.batch_axes_prefix(B) or None
    xspec = P(b, "model", None)
    gspec = sharding.resolve_spec(("embed", "mlp"), params["up"]["w"].shape,
                                  "param")
    dspec = sharding.resolve_spec(("mlp", "embed"), params["down"]["w"].shape,
                                  "param")

    def inner(x_l, wg, wu, wd):
        wu = _gather_w(wu, ("embed", "mlp"))
        wd = _gather_w(wd, ("mlp", "embed"))
        x_f = lax.all_gather(x_l, "model", axis=1, tiled=True)   # SP "g"
        if has_gate:
            wg = _gather_w(wg, ("embed", "mlp"))
            h = f(jnp.einsum("bsd,df->bsf", x_f, wg)) \
                * jnp.einsum("bsd,df->bsf", x_f, wu)
        else:
            h = f(jnp.einsum("bsd,df->bsf", x_f, wu))
        y = jnp.einsum("bsf,fd->bsd", h, wd)                     # partial
        return lax.psum_scatter(y, "model", scatter_dimension=1, tiled=True)

    wg = params["gate"]["w"] if has_gate else params["up"]["w"]
    specs = (xspec, gspec, gspec, dspec)
    fsp = shard_map(inner, mesh=mesh, in_specs=specs, out_specs=xspec,
                    check_vma=False)
    return fsp(x, wg, params["up"]["w"], params["down"]["w"])
