#!/usr/bin/env python3
"""Smoke run of the disaggregated serving cluster on a TPU, at the full
published width of phi4-mini-3.8b (32 layers, d_model 3072, 24 / 8
heads, d_ff 8192, vocab 200064, bf16). No weights ship with the repo:
the parameters are drawn by ``model.init`` from ``--seed``, so this run
proves the path, not output quality.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one pod per chip, vs one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

One chip, three phases:

  * oracle   — a single-pod paged `ServeEngine` on the scalar
               (``vectorized=False``) verbs datapath serves 8 seeded
               requests (prompts of 64-512 tokens, 16 new tokens each);
  * cluster  — the same requests through `Router` -> 2 `PrefillPod`s
               -> RDMA_WRITE page migration -> 2 paged `ServeEngine`s on
               one `verbs.Fabric`; its tokens must equal the oracle's;
  * logits   — the first paged decode step of the shortest request
               against a float32 run of the model's dense `decode_step`
               (same params upcast, on the host CPU): relative L2 error
               at most `LOGITS_RTOL`.

``--chips 4`` runs only the cluster, with each of the 4 pods (and its
params and page pool) on its own chip, so every migration crosses
chips, against the oracle on one chip.

Lines starting with ``[smoke]`` are a smoke run's diagnostics, not
benchmark numbers. The last line of stdout is one JSON object, printed
only when every phase passed. Without a TPU (``--tiny`` aside), on any
mismatch, or on any exception the script exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the float32 reference runs on the host CPU next to the TPU backend
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"

import jax                                           # noqa: E402
import jax.numpy as jnp                              # noqa: E402
import numpy as np                                   # noqa: E402

from repro import compile_cache, verbs               # noqa: E402
from repro.configs.base import get_config, reduced   # noqa: E402
from repro.models.registry import build_model        # noqa: E402
from repro.obs import metrics                        # noqa: E402
from repro.serve.engine import ServeEngine           # noqa: E402
from repro.serve.kvcache import pad_caches           # noqa: E402
from repro.serve.paged import (PagePool, bucket_len,  # noqa: E402
                               make_paged_step)
from repro.serve.pd_disagg import PrefillPod         # noqa: E402
from repro.serve.router import Router                # noqa: E402

ARCH = "phi4-mini-3.8b"
PREFILL_GIDS = ["pod0/dev0", "pod1/dev0"]
DECODE_GIDS = ["pod2/dev0", "pod3/dev0"]
# bf16 activations and caches against float32 through 32 layers: the
# error measured on the CPU at narrower widths of this architecture is
# 0.015-0.019; 0.05 leaves room for full width and the MXU's rounding
LOGITS_RTOL = 0.05
# lowering to MLIR and XLA compilation (or a compile-cache load); Python
# tracing nests across jits and stays in the run time
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass(frozen=True)
class Size:
    max_batch: int
    max_seq: int
    page_tokens: int
    n_requests: int
    prompt_lens: tuple[int, int]     # inclusive range
    max_new: int


FULL = Size(max_batch=8, max_seq=1024, page_tokens=16, n_requests=8,
            prompt_lens=(64, 512), max_new=16)
TINY = Size(max_batch=8, max_seq=64, page_tokens=8, n_requests=8,
            prompt_lens=(4, 24), max_new=6)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str):
    print(f"[smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


class Clock:
    """Wall seconds per phase, split into compile (`COMPILE_EVENTS`,
    from JAX's monitoring events) and the rest."""

    def __init__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += secs

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        log(f"phase {name}: {wall:.3f} s wall = {comp:.3f} s compile + "
            f"{wall - comp:.3f} s run")


def fused_launches() -> int:
    return int(metrics.get_registry().snapshot().get("fused/launches", 0))


def make_prompts(seed: int, size: Size, vocab: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    lo, hi = size.prompt_lens
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1))).tolist()
            for _ in range(size.n_requests)]


def serve_oracle(model, params, prompts, size: Size) -> list[list[int]]:
    eng = ServeEngine(model, params, max_batch=size.max_batch,
                      max_seq=size.max_seq, vectorized=False,
                      page_tokens=size.page_tokens)
    rids = [eng.submit(p, max_new_tokens=size.max_new) for p in prompts]
    res = eng.run_until_done()
    eng.close()
    return [res[r] for r in rids]


def build_cluster(model, params, size: Size):
    fabric = verbs.Fabric(pods=len(PREFILL_GIDS) + len(DECODE_GIDS))
    router = Router(fabric)
    for g in DECODE_GIDS:
        router.add_decode(ServeEngine(
            model, params, max_batch=size.max_batch, max_seq=size.max_seq,
            fabric=fabric, gid=g, service=f"serve/{g}",
            page_tokens=size.page_tokens))
    for g in PREFILL_GIDS:
        router.add_prefill(PrefillPod(
            model, params, fabric=fabric, gid=g, decode_gids=DECODE_GIDS,
            max_seq=size.max_seq, page_tokens=size.page_tokens))
    return router


def serve_cluster(router, prompts, size: Size) -> list[list[int]]:
    rids = [router.submit(p, max_new_tokens=size.max_new) for p in prompts]
    res = router.run_until_done()
    missing = [r for r in rids if r not in res]
    if missing:
        fail(f"cluster left requests {missing} unfinished")
    return [res[r] for r in rids]


def check_tokens(name: str, got, expect) -> None:
    bad = [i for i, (g, e) in enumerate(zip(got, expect)) if g != e]
    if bad or len(got) != len(expect):
        fail(f"{name}: tokens differ from the single-pod oracle in "
             f"requests {bad}: {got[bad[0]] if bad else got} vs "
             f"{expect[bad[0]] if bad else expect}")
    log(f"{name}: {len(got)} requests, {sum(map(len, got))} tokens, "
        f"equal to the single-pod oracle")


def paged_first_logits(model, params, prompt, size: Size):
    """bf16: bucketed prefill, pages filled into a `PagePool`, one
    `make_paged_step` decode step — a decode pod's path for one slot.
    Returns (logits (V,) float32, the token fed to the step)."""
    fabric = verbs.Fabric()
    pool = PagePool(model, fabric.node(fabric.gids[0]).pd,
                    max_batch=size.max_batch, max_seq=size.max_seq,
                    page_tokens=size.page_tokens)
    plen = len(prompt)
    padded = np.zeros((1, bucket_len(plen, size.max_seq)), np.int32)
    padded[0, :plen] = prompt
    logits, caches = jax.jit(model.prefill)(
        params, padded, last_pos=np.asarray([plen - 1], np.int32))
    first = int(jnp.argmax(logits[0, -1]))
    ids = pool.alloc(pool.pages_for(plen + 1))
    pool.fill(ids[:pool.pages_for(plen)], caches)
    pool.bind_slot(0, ids)
    tokens = np.zeros((size.max_batch, 1), np.int32)
    tokens[0, 0] = first
    pos = np.ones((size.max_batch,), np.int32)
    pos[0] = plen                                   # the write index
    step = make_paged_step(model, pool)
    out, _ = step(params, tokens, pool.table, pos, pool.regions())
    out = np.asarray(out[0, 0], np.float32)
    pool.close()
    return out, first


def f32_dense_logits(model, params, prompt, first: int):
    """float32 reference on the host CPU: the same params upcast, dense
    prefill, then the model's dense `decode_step` on `first`."""
    cpu = jax.devices("cpu")[0]
    model32 = build_model(dataclasses.replace(model.cfg, dtype="float32"))
    p32 = jax.tree.map(lambda a: jax.device_put(a, cpu).astype(jnp.float32),
                       params)
    plen = len(prompt)
    tokens = jax.device_put(np.asarray([prompt], np.int32), cpu)
    _, caches = jax.jit(model32.prefill)(p32, tokens)
    caches = pad_caches(caches, plen, plen + 1)
    out, _ = jax.jit(model32.decode_step)(
        p32, jax.device_put(np.asarray([[first]], np.int32), cpu), caches,
        jax.device_put(np.int32(plen), cpu))
    return np.asarray(out[0, 0], np.float32)


def report_memory(devices, when: str) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        log(f"{d}: peak_bytes_in_use {when}: "
            f"{peak if peak is not None else 'not reported'}")


def run_one_chip(model, params, prompts, size: Size, clock: Clock) -> None:
    with clock.phase("oracle"):
        expect = serve_oracle(model, params, prompts, size)
    log(f"oracle: {len(expect)} requests, {sum(map(len, expect))} tokens")

    router = build_cluster(model, params, size)
    launches0 = fused_launches()
    with clock.phase("cluster"):
        got = serve_cluster(router, prompts, size)
    check_tokens("cluster", got, expect)
    pages = sum(p.kv.pages_migrated for p in router.prefill_pods)
    if pages <= 0:
        fail("cluster migrated no KV pages")
    log(f"cluster: {pages} KV page records migrated over RDMA_WRITE, "
        f"fused/launches +{fused_launches() - launches0}, "
        f"failovers {router.failovers}")
    router.close()

    i = min(range(len(prompts)), key=lambda j: len(prompts[j]))
    with clock.phase("logits"):
        got, first = paged_first_logits(model, params, prompts[i], size)
        ref = f32_dense_logits(model, params, prompts[i], first)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        fail("non-finite logits")
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    log(f"logits: request {i} ({len(prompts[i])} tokens), first paged "
        f"decode step vs float32 dense decode_step: rel L2 {rel!r} "
        f"(limit {LOGITS_RTOL}), argmax {int(got.argmax())} vs "
        f"{int(ref.argmax())}")
    if rel > LOGITS_RTOL:
        fail(f"bf16 logits off the float32 reference: rel L2 {rel!r}")


def run_four_chips(model, params, prompts, size: Size, clock: Clock):
    with clock.phase("oracle (one chip)"):
        expect = serve_oracle(model, params, prompts, size)
    router = build_cluster(model, params, size)
    pods = list(router.prefill_pods) + list(router.engines.values())
    placed = {}
    for pod in pods:
        where = {d for r in pod.pool.regions() for d in r.devices()}
        if pod.device is None or where != {pod.device}:
            fail(f"{pod.gid}: page pool on {where}, pod on {pod.device}")
        placed[pod.gid] = pod.device
        log(f"{pod.gid}: params and page pool on {pod.device}")
    if len(set(placed.values())) != len(pods):
        fail(f"pods share chips: {placed}")
    launches0 = fused_launches()
    with clock.phase("cluster (4 chips)"):
        got = serve_cluster(router, prompts, size)
    check_tokens("cluster (4 chips)", got, expect)
    for g, eng in router.engines.items():
        if eng.pool.pages_allocated <= 0:
            fail(f"decode pod {g} received no migrated pages")
    pages = sum(p.kv.pages_migrated for p in router.prefill_pods)
    log(f"cluster (4 chips): {pages} KV page records migrated from "
        f"{[str(placed[g]) for g in PREFILL_GIDS]} to "
        f"{[str(placed[g]) for g in DECODE_GIDS]}, fused/launches "
        f"+{fused_launches() - launches0}")
    router.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="reduced() config on any backend (rehearsal)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    log(f"devices: {devices}")
    if devices[0].platform != "tpu" and not args.tiny:
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    if args.chips == 4 and len(devices) != 4:
        fail(f"--chips 4 needs 4 devices, JAX found {len(devices)}")
    log(f"compile cache: {compile_cache.enable()}")
    clock = Clock()
    size = TINY if args.tiny else FULL
    cfg = get_config(ARCH)
    if args.tiny:
        cfg = reduced(cfg)
    model = build_model(cfg)
    with clock.phase("init"):
        params = model.init(jax.random.PRNGKey(args.seed))
        jax.block_until_ready(params)
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.dtype}, parameter bytes {n_bytes}")
    report_memory(devices[:1], "after init")
    prompts = make_prompts(args.seed, size, cfg.vocab_size)
    log(f"prompt lengths: {[len(p) for p in prompts]}, "
        f"{size.max_new} new tokens each")

    if args.chips == 4:
        run_four_chips(model, params, prompts, size, clock)
    else:
        run_one_chip(model, params, prompts, size, clock)
    report_memory(devices[:args.chips], "at the end")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
