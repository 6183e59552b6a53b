"""Mixture-of-Experts with FlexiNS-style header/payload-split dispatch.

The paper's T1 (header-only offloading TX) maps 1:1 onto MoE dispatch:

  * header  = routing metadata (top-k expert ids, weights, slot positions)
    — computed on the *control path*, outside the payload shard_map, tiny;
  * payload = hidden states — moved **exactly once**, directly, via
    all_to_all over the expert-parallel (`model`) axis into per-expert
    capacity slots, with no staging through a replicated buffer.

Three implementations (MoEConfig/impl selection):
  'a2a'        — sequence-parallel tokens, direct all_to_all dispatch
                 (FlexiNS-faithful path; default on a mesh).
  'replicated' — tokens replicated over the expert axis; each rank gathers
                 its experts' tokens locally and the combined output is
                 psum'd. This is the *staged* baseline: payload bytes ride
                 a full-activation all-reduce (the "Arm buffer" analogue).
                 Also the decode-time path (1 token/step).
  'local'      — single-device python loop over experts (reference oracle,
                 smoke tests).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.models.layers import act_fn
from repro.models.module import Spec
from repro.models import ffn
from repro.parallel import sharding


# --------------------------------------------------------------------------
# Specs
# --------------------------------------------------------------------------
def moe_spec(cfg) -> dict:
    m = cfg.moe
    E, D, F = m.n_experts, cfg.d_model, m.d_ff_expert
    s = {
        # router stays replicated: every rank must see all logits (header path)
        "router": {"w": Spec((D, E), (None, None), dtype="float32")},
        "experts": {
            "gate": Spec((E, D, F), ("expert", "embed", "expert_mlp")),
            "up": Spec((E, D, F), ("expert", "embed", "expert_mlp")),
            "down": Spec((E, F, D), ("expert", "expert_mlp", "embed")),
        },
    }
    if _router_type(cfg) == "sigmoid_bias":
        s["router"]["bias"] = Spec((E,), (None,), init="zeros", dtype="float32")
    if m.n_shared:
        s["shared"] = ffn.ffn_spec(D, m.n_shared * m.d_ff_shared, cfg.act)
    return s


def _router_type(cfg) -> str:
    # deepseek-style sigmoid+bias routing for MLA archs, softmax otherwise
    return "sigmoid_bias" if cfg.use_mla else "softmax"


# --------------------------------------------------------------------------
# Routing (the "header" computation — control path)
# --------------------------------------------------------------------------
def route(params, x, cfg):
    """x: (..., D) -> (weights (..., k) f32, idx (..., k) i32, aux f32)."""
    m = cfg.moe
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        params["router"]["w"])
    if _router_type(cfg) == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        sel = scores + params["router"]["bias"]
        _, idx = lax.top_k(sel, m.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = lax.top_k(probs, m.top_k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
    # switch-style load-balance aux: E * sum_e f_e * p_e (scatter-add, not
    # a (T, E) one-hot materialization)
    E = m.n_experts
    idx_f = idx.reshape(-1)
    counts = jnp.zeros((E,), jnp.float32).at[idx_f].add(1.0)
    f_e = counts / jnp.maximum(idx_f.shape[0], 1)
    p_e = probs.reshape(-1, E).mean(0)
    aux = E * jnp.sum(f_e * p_e)
    return w, idx, aux


# --------------------------------------------------------------------------
# Expert FFN on capacity slots
# --------------------------------------------------------------------------
def _experts_ffn(w_gate, w_up, w_down, h, act):
    f = act_fn(act)
    g = jnp.einsum("ecd,edf->ecf", h, w_gate)
    u = jnp.einsum("ecd,edf->ecf", h, w_up)
    return jnp.einsum("ecf,efd->ecd", f(g) * u, w_down)


def _gather_fsdp(w, spec_axes, shape):
    """all_gather away any non-expert-dim param sharding inside shard_map
    (ZeRO-3 weight gather). The expert dim itself stays sharded (EP)."""
    spec = sharding.resolve_spec(spec_axes, shape, "param")
    for d, ent in enumerate(spec):
        if ent is None or spec_axes[d] == "expert":
            continue
        for ax in ((ent,) if isinstance(ent, str) else ent):
            if ax != "model":
                w = lax.all_gather(w, ax, axis=d, tiled=True)
    return w


def _capacity(tokens: int, cfg) -> int:
    from repro.perf import FLAGS
    m = cfg.moe
    cf = FLAGS.capacity_factor if FLAGS.capacity_factor is not None \
        else m.capacity_factor
    c = int(math.ceil(tokens * m.top_k * cf / m.n_experts))
    return max(4, -(-c // 4) * 4)      # round up to a multiple of 4


# --------------------------------------------------------------------------
# Implementations
# --------------------------------------------------------------------------
def moe_apply(params, x, cfg, *, sp: bool = False):
    """x: (B, S, D) -> (y, aux_loss). Auto-selects implementation."""
    m = cfg.moe
    ctx = sharding.current()
    M = sharding.mesh_axis_size("model")
    B, S, D = x.shape

    w, idx, aux = route(params, x, cfg)          # header: control path

    from repro.perf import FLAGS
    if ctx is None or M == 1 or m.n_experts % M:
        y = _moe_local(params, x, w, idx, cfg)
    elif S % M == 0 and FLAGS.moe_impl == "a2a":
        y = _moe_a2a(params, x, w, idx, cfg)
    else:
        y = _moe_replicated(params, x, w, idx, cfg)

    if m.n_shared:
        y = y + ffn.ffn_apply(params["shared"], x, cfg.act, sp=sp)
    return y, aux


def _moe_local(params, x, w, idx, cfg):
    """Reference oracle: dense loop over experts (tests / tiny configs)."""
    m = cfg.moe
    B, S, D = x.shape
    y = jnp.zeros_like(x, dtype=jnp.float32)
    ex = params["experts"]
    f = act_fn(cfg.act)
    for e in range(m.n_experts):
        we = jnp.where(idx == e, w, 0.0).sum(-1)          # (B,S)
        h = f(jnp.einsum("bsd,df->bsf", x, ex["gate"][e])) \
            * jnp.einsum("bsd,df->bsf", x, ex["up"][e])
        he = jnp.einsum("bsf,fd->bsd", h, ex["down"][e])
        y = y + we[..., None] * he.astype(jnp.float32)
    return y.astype(x.dtype)


def _dispatch_indices(idx_flat, w_flat, E, C):
    """Compute per-assignment slot positions (the header's 'WQE').

    idx_flat: (A,) expert id per assignment; returns (slot (A,), keep (A,)).
    """
    A = idx_flat.shape[0]
    one_hot = jax.nn.one_hot(idx_flat, E, dtype=jnp.int32)          # (A, E)
    pos = jnp.cumsum(one_hot, axis=0) - 1                           # (A, E)
    pos = jnp.take_along_axis(pos, idx_flat[:, None], axis=1)[:, 0]  # (A,)
    keep = pos < C
    slot = jnp.where(keep, idx_flat * C + pos, E * C)               # OOB drop
    return slot, keep


def _batch_shards(mesh, B):
    bs = 1
    for ax in sharding.batch_axes_prefix(B):
        bs *= mesh.shape[ax]
    return bs


def _ep_axes(cfg, mesh):
    """Mesh axes the expert dim shards over (('model',) or ('model','data'))."""
    ex_shape = (cfg.moe.n_experts, cfg.d_model, cfg.moe.d_ff_expert)
    spec = sharding.resolve_spec(("expert", "embed", "expert_mlp"),
                                 ex_shape, "param")
    ent = spec[0]
    if ent is None:
        return ("model",)
    return (ent,) if isinstance(ent, str) else tuple(ent)


def _moe_a2a(params, x, w, idx, cfg):
    """FlexiNS path: SP tokens + direct all_to_all payload movement over
    the full expert-parallel group (model, or model x data for EP=256)."""
    m = cfg.moe
    ctx = sharding.current()
    mesh = ctx.mesh
    M = mesh.shape["model"]
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    ep = _ep_axes(cfg, mesh)
    ep_size = 1
    for ax in ep:
        ep_size *= mesh.shape[ax]
    E_loc = E // ep_size
    # capacity is per LOCAL shard: tokens this device owns after SP slicing
    T_loc = (B // _batch_shards(mesh, B)) * (S // M)
    C = _capacity(T_loc, cfg)
    b = sharding.batch_axes_prefix(B) or None

    xspec = P(b, "model", None)
    hspec = P(b, "model", None)          # idx/w: (B, S, k)
    ex = params["experts"]
    gspec = sharding.resolve_spec(("expert", "embed", "expert_mlp"),
                                  ex["gate"].shape, "param")
    dspec = sharding.resolve_spec(("expert", "expert_mlp", "embed"),
                                  ex["down"].shape, "param")

    def inner(x_l, w_l, idx_l, wg, wu, wd):
        wg = _gather_fsdp(wg, ("expert", "embed", "expert_mlp"), ex["gate"].shape)
        wu = _gather_fsdp(wu, ("expert", "embed", "expert_mlp"), ex["up"].shape)
        wd = _gather_fsdp(wd, ("expert", "expert_mlp", "embed"), ex["down"].shape)
        Bl, Sl, _ = x_l.shape
        xt = x_l.reshape(Bl * Sl, D)
        idx_f = idx_l.reshape(-1)                      # (A,) A = T_loc*k
        w_f = w_l.reshape(-1)
        slot, keep = _dispatch_indices(idx_f, w_f, E, C)
        payload = jnp.repeat(xt, k, axis=0)            # (A, D)
        disp = jnp.zeros((E * C, D), x_l.dtype).at[slot].set(
            payload, mode="drop").reshape(E, C, D)
        # --- the wire: payload moves exactly once, src shard -> expert shard
        axis = ep if len(ep) > 1 else ep[0]
        disp = lax.all_to_all(disp, axis, split_axis=0, concat_axis=1,
                              tiled=True)              # (E_loc, ep*C, D)
        out = _experts_ffn(wg, wu, wd, disp, cfg.act)
        out = lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                             tiled=True)               # (E, C, D)
        got = jnp.take(out.reshape(E * C, D), slot, axis=0, mode="fill",
                       fill_value=0)                   # (A, D)
        got = got * w_f[:, None].astype(got.dtype)
        y = got.reshape(Bl * Sl, k, D).sum(1)
        return y.reshape(Bl, Sl, D)

    f = shard_map(inner, mesh=mesh,
                  in_specs=(xspec, hspec, hspec, gspec, gspec, dspec),
                  out_specs=xspec, check_vma=False)
    x_sp = sharding.constrain(x, "batch", "kv_seq", None)
    y = f(x_sp, w.astype(x.dtype), idx, ex["gate"], ex["up"], ex["down"])
    return sharding.constrain(y, "batch", "seq", None)


def _moe_replicated(params, x, w, idx, cfg):
    """Staged baseline: tokens replicated over expert axis, psum combine."""
    m = cfg.moe
    ctx = sharding.current()
    mesh = ctx.mesh
    M = mesh.shape["model"]
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    ep = _ep_axes(cfg, mesh)
    ep_size = 1
    for ax in ep:
        ep_size *= mesh.shape[ax]
    E_loc = E // ep_size
    b_axes = sharding.batch_axes_prefix(B)
    # EP=256: tokens must be gathered over data iff the batch shards there
    gather_data = "data" in ep and "data" in b_axes
    bs = _batch_shards(mesh, B)
    # tokens are replicated over `model` but the batch is data-sharded
    T = (B // bs) * (mesh.shape["data"] if gather_data else 1) * S
    C = _capacity(T, cfg)
    b = b_axes or None

    xspec = P(b, None, None)
    hspec = P(b, None, None)
    ex = params["experts"]
    gspec = sharding.resolve_spec(("expert", "embed", "expert_mlp"),
                                  ex["gate"].shape, "param")
    dspec = sharding.resolve_spec(("expert", "expert_mlp", "embed"),
                                  ex["down"].shape, "param")

    def inner(x_l, w_l, idx_l, wg, wu, wd):
        wg = _gather_fsdp(wg, ("expert", "embed", "expert_mlp"), ex["gate"].shape)
        wu = _gather_fsdp(wu, ("expert", "embed", "expert_mlp"), ex["up"].shape)
        wd = _gather_fsdp(wd, ("expert", "expert_mlp", "embed"), ex["down"].shape)
        if gather_data:
            # EP over data too: every expert owner must see all tokens
            x_l = lax.all_gather(x_l, "data", axis=0, tiled=True)
            w_l = lax.all_gather(w_l, "data", axis=0, tiled=True)
            idx_l = lax.all_gather(idx_l, "data", axis=0, tiled=True)
        r = lax.axis_index(ep[0])
        for ax in ep[1:]:
            r = r * mesh.shape[ax] + lax.axis_index(ax)
        Bl, Sl, _ = x_l.shape
        xt = x_l.reshape(Bl * Sl, D)
        # keep only assignments bound for this rank's experts; foreign ones
        # are routed to a dummy expert id E_loc whose slots land past the
        # real buffer and are dropped by the OOB scatter mode
        idx_all = idx_l.reshape(-1)
        loc = (idx_all >= r * E_loc) & (idx_all < (r + 1) * E_loc)
        idx_f = jnp.where(loc, idx_all - r * E_loc, E_loc)
        w_f = jnp.where(loc, w_l.reshape(-1), 0.0)
        slot, keep = _dispatch_indices(idx_f, w_f, E_loc + 1, C)
        payload = jnp.repeat(xt, k, axis=0)
        buf = jnp.zeros((E_loc * C, D), x_l.dtype).at[slot].set(
            payload, mode="drop")                   # dummy slots are OOB here
        disp = buf.reshape(E_loc, C, D)
        out = _experts_ffn(wg, wu, wd, disp, cfg.act)
        got = jnp.take(out.reshape(E_loc * C, D), slot, axis=0, mode="fill",
                       fill_value=0)
        got = got * w_f[:, None].astype(got.dtype)
        y = got.reshape(Bl * Sl, k, D).sum(1).reshape(Bl, Sl, D)
        y = lax.psum(y, ep if len(ep) > 1 else ep[0])   # staged combine
        if gather_data:
            i = lax.axis_index("data")
            B_shard = Bl // mesh.shape["data"]
            y = lax.dynamic_slice_in_dim(y, i * B_shard, B_shard, axis=0)
        return y

    f = shard_map(inner, mesh=mesh,
                  in_specs=(xspec, hspec, hspec, gspec, gspec, dspec),
                  out_specs=xspec, check_vma=False)
    return f(x, w.astype(x.dtype), idx, ex["gate"], ex["up"], ex["down"])
