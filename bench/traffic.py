"""The one traffic generator: turns a mix file (parameters only) and a
seed into the requests of one run.

A mix is a replayed trace. Its arrivals and sizes are one realisation
of the mix's distributions, drawn from the mix's own `trace_seed`, the
same in every run, with nothing smoothed or stratified: open loop, the
arrival times of a Poisson process of the mix's rate over the window,
conditioned on its count (rate x seconds times, each uniform over the
window, sorted; so the offered load is the rate the mix states, and
bursts fall where the draw puts them), then each request's prompt and
output lengths in arrival order. The run's `--seed` draws the token ids
of the prompts (uniform over the vocabulary) and, elsewhere, the
weights, never the work: with the order of arrivals and sizes drawn
from the run's seed, the 45 s windows of one cell spread by 20% in
their p90 TTFT from seed to seed, four times as much as two runs of one
seed do, so the seed was changing what the window measures.

Arrivals are ``"poisson"`` (open loop: independent users at a fixed
rate) or ``"closed"`` (a fixed number of clients, each sending its next
request when the last one finished; `rounds` requests per client are
drawn, client by client within each round).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # int32 token ids
    max_new: int                # output tokens, the first one included
    at: float | None = None     # open loop: scheduled offset in the window
    client: int | None = None   # closed loop: which client sends it


@dataclasses.dataclass
class Traffic:
    arrivals: str
    requests: list[Request]     # open loop: in arrival order
    rounds: list[list[Request]]  # closed loop: rounds[r][client]

    def all_requests(self) -> list[Request]:
        return self.requests + [r for rnd in self.rounds for r in rnd]


def draw(dist: dict, rng: np.random.Generator) -> int:
    """One length from `dist`: integers lo..hi inclusive, uniform or
    log-uniform."""
    kind, lo, hi = dist["dist"], dist["lo"], dist["hi"]
    if kind == "uniform":
        return int(rng.integers(lo, hi + 1))
    if kind == "loguniform":
        v = math.exp(rng.uniform(math.log(lo), math.log(hi + 1)))
        return min(int(v), hi)
    raise ValueError(f"unknown distribution {kind!r}")


def generate(mix: dict, *, vocab: int, seed: int, seconds: float) -> Traffic:
    trace = np.random.default_rng(int(mix["trace_seed"]))
    tokens = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    arrivals = mix["arrivals"]
    sizes, at = [], []
    if arrivals == "poisson":
        n = max(1, round(float(mix["rate_per_s"]) * seconds))
        at = np.sort(trace.uniform(0.0, seconds, n)).tolist()
        sizes = [(draw(mix["prompt_tokens"], trace),
                  draw(mix["output_tokens"], trace)) for _ in range(n)]
    elif arrivals == "closed":
        clients = int(mix["clients"])
        sizes = [(draw(mix["prompt_tokens"], trace),
                  draw(mix["output_tokens"], trace))
                 for _ in range(clients * int(mix["rounds"]))]
    else:
        raise ValueError(f"unknown arrivals {arrivals!r}")
    reqs = [Request(tokens.integers(0, vocab, p, dtype=np.int32), o)
            for p, o in sizes]
    if arrivals == "poisson":
        for r, t in zip(reqs, at):
            r.at = t
        return Traffic(arrivals, reqs, [])
    rounds = [reqs[i:i + clients] for i in range(0, len(reqs), clients)]
    for rnd in rounds:
        for c, r in enumerate(rnd):
            r.client = c
    return Traffic(arrivals, [], rounds)
