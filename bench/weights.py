"""Weights from the seed, made on the device in ONE jitted call, in the
type they are served in.

The tree has the program's layout (its leaf names and shapes, read
with `jax.eval_shape` of its own init, which allocates nothing); the
values are the benchmark's own. Each leaf's rule is looked up by its
path in `RULES`, so a leaf the benchmark does not know is an error,
not a silent default. The plain reference reads the same tree by the
same names.
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


def _names(path) -> tuple[str, ...]:
    out = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            out.append(str(k.key))
    return tuple(out)


def rule(path) -> tuple[str, tuple | None]:
    names = _names(path)
    r = RULES.get(names[-2:])
    if r is None:
        raise KeyError(f"no weight rule for leaf {'/'.join(names)}")
    return r


def key_data(seed: int, stream: int) -> np.ndarray:
    """Key material for jax's "rbg" generator from a seed of any size."""
    return np.random.SeedSequence([seed, stream]).generate_state(
        4, np.uint32)


def make(model, seed: int):
    """The program's parameter tree filled from `seed`, in one jitted
    call on the default device."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [rule(p) for p, _ in leaves]

    def build(kd):
        keys = jax.random.split(jax.random.wrap_key_data(kd, impl="rbg"),
                                len(leaves))
        out = []
        for (_, sd), (init, fan_dims), k in zip(leaves, rules, keys):
            if init == "ones":
                out.append(jnp.ones(sd.shape, sd.dtype))
                continue
            fan = int(np.prod([sd.shape[d] for d in fan_dims]))
            x = jax.random.normal(k, sd.shape, jnp.float32) * fan ** -0.5
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key_data(seed, 0))
