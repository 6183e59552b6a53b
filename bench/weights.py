"""Weights from the seed, made on the device in ONE jitted call, in the
type they are served in.

The tree has the program's layout (its leaf names and shapes, read
with `jax.eval_shape` of its own init, which allocates nothing); the
values are the benchmark's own. Each leaf's rule is looked up by its
last two path names in `RULES`, then in the configuration file's own
``weight_rules`` (the same form, keyed ``"a/b"``), so a configuration
whose block has leaves the benchmark does not know brings their rules
as data. A leaf found in neither is an error, not a silent default; so
is a file rule that redefines one of `RULES` or that no leaf uses. The
plain reference reads the same tree by the same names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# last path names -> (init, which dims make the fan-in). std is
# 1/sqrt(fan-in) for every matrix, the embedding tables too (fan-in of
# the output head: the hidden size). The residual stream is then
# carried by the layers' outputs and not by the embedded token, so the
# next token depends on the context: with unit-RMS embeddings and a tied
# head, a random model only repeats its input token, which no
# comparison of tokens can tell from a broken cache.
RULES = {
    ("ln1", "scale"): ("ones", None),
    ("ln2", "scale"): ("ones", None),
    ("final_norm", "scale"): ("ones", None),
    ("embed", "table"): ("normal", (-1,)),        # (V, D)
    ("out_embed", "table"): ("normal", (-1,)),
    ("wq", "w"): ("normal", (-3,)),         # (..., D, H, hd)
    ("wk", "w"): ("normal", (-3,)),
    ("wv", "w"): ("normal", (-3,)),
    ("wo", "w"): ("normal", (-3, -2)),      # (..., H, hd, D)
    ("gate", "w"): ("normal", (-2,)),       # (..., D, F)
    ("up", "w"): ("normal", (-2,)),
    ("down", "w"): ("normal", (-2,)),       # (..., F, D)
}
INITS = ("normal", "ones", "zeros")


def _names(path) -> tuple[str, ...]:
    out = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            out.append(str(k.key))
    return tuple(out)


def file_rules(cfg: dict) -> dict:
    """The configuration file's ``weight_rules``, keyed as `RULES` is."""
    out = {}
    for name, (init, fan_dims) in cfg.get("weight_rules", {}).items():
        key = tuple(name.split("/"))
        if len(key) != 2:
            raise ValueError(f"weight rule {name!r}: not two path names")
        if key in RULES:
            raise ValueError(f"weight rule {name!r} in the configuration "
                             f"file redefines the benchmark's own")
        if init not in INITS or (init == "normal") != bool(fan_dims):
            raise ValueError(f"weight rule {name!r}: {init!r} with fan-in "
                             f"dims {fan_dims!r}")
        out[key] = (init, tuple(fan_dims) if fan_dims else None)
    return out


def rules(leaves, cfg: dict) -> list[tuple[str, tuple | None]]:
    """Each leaf's rule, from `RULES` and then the file's own."""
    extra = file_rules(cfg)
    out, used = [], set()
    for path, _ in leaves:
        names = _names(path)
        r = RULES.get(names[-2:])
        if r is None:
            r = extra.get(names[-2:])
            used.add(names[-2:])
        if r is None:
            raise KeyError(f"no weight rule for leaf {'/'.join(names)}")
        out.append(r)
    unused = sorted("/".join(k) for k in extra.keys() - used)
    if unused:
        raise ValueError(f"weight rules no leaf uses: {unused}")
    return out


def key_data(seed: int, stream: int) -> np.ndarray:
    """Key material for jax's "rbg" generator from a seed of any size."""
    return np.random.SeedSequence([seed, stream]).generate_state(
        4, np.uint32)


def make(model, seed: int, cfg: dict):
    """The program's parameter tree filled from `seed`, in one jitted
    call on the default device; `cfg` is the configuration file."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaf_rules = rules(leaves, cfg)

    def build(kd):
        keys = jax.random.split(jax.random.wrap_key_data(kd, impl="rbg"),
                                len(leaves))
        out = []
        for (_, sd), (init, fan_dims), k in zip(leaves, leaf_rules, keys):
            if init != "normal":
                fill = jnp.ones if init == "ones" else jnp.zeros
                out.append(fill(sd.shape, sd.dtype))
                continue
            fan = int(np.prod([sd.shape[d] for d in fan_dims]))
            x = jax.random.normal(k, sd.shape, jnp.float32) * fan ** -0.5
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key_data(seed, 0))
