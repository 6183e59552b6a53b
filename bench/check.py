"""The comparison that decides `correct`.

Once the window has closed and the cluster's state is freed, a sample of
the requests it finished (drawn from the seed, the longest one always
in it, some hundreds of served tokens) goes through the configuration's
plain float32 reference: one causal forward over each prompt followed
by its served tokens. Each served token is judged by its GAP, the
reference's best logit at that position minus the reference's logit of
the served token (0 when the served token is the reference's argmax).
The first served token comes from the prefill pod, every later one from
a paged decode step reading the pages the migration landed, so the
sample covers prefill, migration and decode.

The number compared is the widest gap, ``max_logit_gap``, against the
configuration's limit (``correct_limit`` in its file). The control puts
the reference computed in float8 in the program's place: at every
position of the same prompts and tokens, the gap of the token the
float8 reference puts first.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

MIN_TOKENS = 384        # served tokens in the sample, at least ...
MAX_SEQS = 16           # ... from at most this many requests
ROW_BLOCK = 256


def load_reference(bench_dir: Path, name: str):
    path = Path(bench_dir) / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(recs, seed: int) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until MIN_TOKENS served tokens or MAX_SEQS requests."""
    done = [r for r in recs if r.served]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.req.prompt.size + len(r.served),
                                       -r.rid))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    out, n = [longest], len(longest.served)
    for i in rng.permutation(len(done)):
        if n >= MIN_TOKENS or len(out) >= MAX_SEQS:
            break
        if done[i] is not longest:
            out.append(done[i])
            n += len(done[i].served)
    return out


def batch(picked, max_seq: int):
    """(tokens (MAX_SEQS, S), flat rows, served tokens, first-token
    mask) for the reference: each prompt followed by its served tokens
    but the last; row j of a request is the position that predicts its
    served token j."""
    seqs = [np.concatenate([r.req.prompt,
                            np.asarray(r.served[:-1], np.int32)])
            for r in picked]
    S = 128
    while S < max(s.size for s in seqs):
        S *= 2
    if S > max_seq:
        raise ValueError(f"a sampled sequence is longer than {max_seq}")
    tokens = np.zeros((MAX_SEQS, S), np.int32)
    rows, served, first = [], [], []
    for i, (r, s) in enumerate(zip(picked, seqs)):
        tokens[i, :s.size] = s
        plen = int(r.req.prompt.size)
        for j, t in enumerate(r.served):
            rows.append(i * S + plen - 1 + j)
            served.append(int(t))
            first.append(j == 0)
    return tokens, np.asarray(rows), np.asarray(served), np.asarray(first)


@jax.jit
def _gaps(ref, chosen):
    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return best - got


def readings(weights, cfg: dict, picked, ref_mod, *,
             control: bool = False) -> dict:
    """max_logit_gap of the served tokens (and, with `control`, of the
    float8 reference's own first choices) over the sampled requests."""
    tokens, rows, served, first = batch(picked,
                                        int(cfg["deployment"]["max_seq"]))
    n = rows.size
    pad = -n % ROW_BLOCK
    rows_p = np.concatenate([rows, np.repeat(rows[-1:], pad)])
    served_p = np.concatenate([served, np.repeat(served[-1:], pad)])
    with jax.default_matmul_precision("highest"):
        ref = ref_mod.logits_at(weights, cfg, tokens, rows_p)
        gaps = np.asarray(_gaps(ref, jnp.asarray(served_p)))[:n]
        out = {"max_logit_gap": float(gaps.max()),
               "first_token_gap": float(gaps[first].max()),
               "tokens_compared": int(n),
               "requests_compared": len(picked),
               "served_equal_argmax": float((gaps == 0).mean())}
        if control:
            ctl = ref_mod.logits_at(weights, cfg, tokens, rows_p,
                                    quant="fp8")
            pick = jnp.argmax(ctl, axis=-1).astype(jnp.int32)
            cg = np.asarray(_gaps(ref, pick))[:n]
            out["control_max_logit_gap"] = float(cg.max())
            out["control_equal_argmax"] = float((cg == 0).mean())
    return out
