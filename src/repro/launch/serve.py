"""Serving driver (the paper's flagship kind): batched requests through the
FlexiNS stack — T3 ring submission, prefill, T1 KV transfer (P/D pods),
T2 paged ingest, batched decode.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --requests 6 [--pd] [--quantize-kv]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.configs.base import get_config, reduced as reduce_cfg
from repro.models.registry import build_model
from repro.serve.engine import ServeEngine
from repro.serve.pd_disagg import PDServer


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="gemma-2b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=6)
    p.add_argument("--max-new", type=int, default=12)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=96)
    p.add_argument("--pd", action="store_true",
                   help="prefill/decode disaggregation path")
    p.add_argument("--quantize-kv", action="store_true")
    args = p.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    if args.pd:
        server = PDServer(model, params, max_seq=args.max_seq,
                          page_tokens=8,
                          quantize_bits=8 if args.quantize_kv else 0)
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.requests, 8)).astype(np.int32)
        t0 = time.monotonic()
        toks, stats = server.serve(prompts, n_steps=args.max_new)
        dt = time.monotonic() - t0
        print(f"P/D served {args.requests} requests in {dt:.2f}s; "
              f"KV payload {stats.payload_bytes/1e6:.2f}MB, "
              f"headers {stats.header_bytes}B "
              f"({stats.header_bytes/stats.payload_bytes:.2e} of payload)")
        for i, row in enumerate(toks):
            print(f"req {i}: {row.tolist()}")
        return

    eng = ServeEngine(model, params, max_batch=args.max_batch,
                      max_seq=args.max_seq)
    t0 = time.monotonic()
    for i in range(args.requests):
        plen = int(rng.integers(3, 10))
        eng.submit(rng.integers(0, cfg.vocab_size, plen).tolist(),
                   max_new_tokens=args.max_new)
    results = eng.run_until_done()
    dt = time.monotonic() - t0
    total_toks = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests / {total_toks} tokens "
          f"in {dt:.2f}s ({total_toks/dt:.1f} tok/s); "
          f"ring DMA writes={eng.ring.dma_writes} reads={eng.ring.dma_reads}")
    for rid, toks in results.items():
        print(f"req {rid}: {toks}")


if __name__ == "__main__":
    main()
