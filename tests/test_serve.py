"""Serving: paged pool roundtrip, engine generation, PD-disaggregation
end-to-end invariant (transfer + paged ingest must not change outputs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models.registry import build_model
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import PagedKVPool, pad_caches
from repro.serve.pd_disagg import PDServer


def _model(arch="gemma-2b", key=0):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(key))
    return cfg, model, params


def test_paged_pool_roundtrip():
    pool = PagedKVPool(n_pages=8, page_tokens=4, feature_shape=(2, 8),
                       dtype="float32")
    alloc = pool.allocate(n_tokens=13)           # 4 pages
    kv = jnp.asarray(np.random.default_rng(0)
                     .standard_normal((13, 2, 8)).astype(np.float32))
    pool.ingest(alloc, kv)
    out = pool.gather(alloc, 13)
    np.testing.assert_allclose(np.asarray(out), np.asarray(kv))


def test_paged_pool_roundtrip_with_kernel():
    pool = PagedKVPool(n_pages=8, page_tokens=4, feature_shape=(2, 8),
                       dtype="float32")
    alloc = pool.allocate(n_tokens=16)
    kv = jnp.asarray(np.random.default_rng(1)
                     .standard_normal((16, 2, 8)).astype(np.float32))
    pool.ingest(alloc, kv, use_kernel=True, interpret=True)
    out = pool.gather(alloc, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(kv))


def test_paged_pool_isolation():
    """Two sequences never alias pages (shadow-table invariant)."""
    pool = PagedKVPool(n_pages=8, page_tokens=4, feature_shape=(4,),
                       dtype="float32")
    a1 = pool.allocate(16)
    a2 = pool.allocate(16)
    kv1 = jnp.ones((16, 4))
    kv2 = 2.0 * jnp.ones((16, 4))
    pool.ingest(a1, kv1)
    pool.ingest(a2, kv2)
    np.testing.assert_allclose(np.asarray(pool.gather(a1, 16)), 1.0)
    np.testing.assert_allclose(np.asarray(pool.gather(a2, 16)), 2.0)


def _reference_generate(model, params, prompt, n_new, max_seq):
    """Greedy generation through prefill+decode (the trusted path)."""
    toks = list(prompt)
    logits, caches = model.prefill(params, jnp.asarray([prompt]))
    caches = pad_caches(caches, len(prompt), max_seq)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n_new - 1):
        lg, caches = model.decode_step(params, jnp.asarray([[out[-1]]]),
                                       caches, jnp.int32(pos))
        out.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    return out


def test_serve_engine_matches_reference():
    cfg, model, params = _model()
    eng = ServeEngine(model, params, max_batch=2, max_seq=48)
    prompts = [[5, 3, 9, 1], [7, 7, 2]]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    results = eng.run_until_done()
    for rid, prompt in zip(rids, prompts):
        exp = _reference_generate(model, params, prompt, 6, 48)
        assert results[rid] == exp, (results[rid], exp)


def test_serve_engine_burst_absorbed_by_ring():
    cfg, model, params = _model()
    eng = ServeEngine(model, params, max_batch=2, max_seq=48)
    rids = [eng.submit([1 + i, 2, 3], max_new_tokens=4) for i in range(5)]
    results = eng.run_until_done()
    assert all(len(results[r]) == 4 for r in rids)


@pytest.mark.parametrize("arch", ["gemma-2b", "granite-moe-1b-a400m",
                                  "mamba2-780m", "recurrentgemma-2b"])
def test_pd_disagg_end_to_end_invariant(arch):
    """P/D disaggregation (prefill -> transfer -> paged ingest -> decode)
    must produce exactly the tokens of direct single-node serving."""
    cfg, model, params = _model(arch, key=1)
    server = PDServer(model, params, max_seq=48, page_tokens=8)
    prompts = np.asarray([[4, 8, 15, 16], [23, 42, 3, 7]], np.int32)
    toks, stats = server.serve(prompts, n_steps=5)
    # reference: no transfer, no paging
    for b, prompt in enumerate(prompts):
        exp = _reference_generate(model, params, list(prompt), 6, 48)
        assert toks[b].tolist() == exp, (arch, toks[b].tolist(), exp)
    assert stats.payload_bytes > 0 and stats.header_bytes > 0
    # headers are one 64B descriptor per cache leaf, independent of payload
    # size (at production scale: 64B vs GBs — the header/payload split)
    assert stats.header_bytes == 64 * stats.n_leaves


def test_pd_disagg_with_ingest_kernel():
    cfg, model, params = _model("gemma-2b", key=2)
    server = PDServer(model, params, max_seq=32, page_tokens=8)
    prompts = np.asarray([[4, 8, 15]], np.int32)
    t1, _ = server.serve(prompts, n_steps=3)
    t2, _ = server.serve(prompts, n_steps=3, use_kernel=True,
                         interpret=True)
    np.testing.assert_array_equal(t1, t2)


def test_kvtransfer_many_one_doorbell():
    """transfer_many ships k cache trees as ONE WQE chain: one doorbell,
    aggregated stats, wr_ids continuing the transfer() sequence, trees
    delivered intact."""
    from repro.core.kvtransfer import KVTransferEngine
    cfg, model, params = _model()
    _, caches = model.prefill(params, jnp.ones((2, 8), jnp.int32))
    eng = KVTransferEngine(model, 2, 8)
    one = eng.transfer(caches)                   # wr_id 1
    single_stats = eng.stats
    d0 = eng.ep.qp.doorbell_writes
    outs = eng.transfer_many([caches, caches, caches])   # wr_id 2,3,4
    assert eng.ep.qp.doorbell_writes - d0 == 1
    assert eng._wr_id == 4
    assert eng.stats.payload_bytes == 3 * single_stats.payload_bytes
    assert len(outs) == 3
    for got in outs + [one]:
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b)), got, caches)


def test_cross_engine_shared_fabric_pool():
    """ISSUE 5: serve engine + kvtransfer as tenants of ONE fabric —
    one recv pool, one srq_limit watermark, both run through
    fabric.connect() and both make progress concurrently."""
    from repro import verbs
    from repro.core.kvtransfer import KVTransferEngine
    cfg, model, params = _model()
    fabric = verbs.Fabric()
    eng = ServeEngine(model, params, max_batch=2, max_seq=48,
                      fabric=fabric)
    # single-pod shared fabric: kv transfers move by reference and the
    # engine says so up front
    with pytest.warns(UserWarning, match="single-pod fabric"):
        kv = KVTransferEngine(model, 2, 8, fabric=fabric)
    assert kv.srq is eng.srq is fabric.srq       # ONE fabric-scope pool
    assert kv.fabric is eng.fabric
    # interleave the tenants: transfer mid-serving, then finish serving
    rids = [eng.submit([5, 3, 9], max_new_tokens=4)]
    eng.step()
    _, caches = jax.jit(model.prefill)(params, jnp.ones((2, 8), jnp.int32))
    got = kv.transfer(caches)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b)), got, caches)
    results = eng.run_until_done()
    assert len(results[rids[0]]) == 4
    # both tenants drew from the shared pool (per-QP takes recorded)
    takes = fabric.srq.taken_by_qp
    assert takes[eng.ep.peer.qp.qp_num] >= 1
    assert takes[kv.ep.peer.qp.qp_num] >= 1
    # tenants leaving a LONG-LIVED fabric release everything they held:
    # listeners, QPs, routes, and the serve engine's refill doorbell
    kv.close()
    eng.close()
    assert not fabric.qps and not fabric.routes and not fabric._listeners
    assert not fabric.srq._limit_cbs


def test_pd_quantized_transfer_close():
    """int8 wire compression: outputs may differ slightly but the first
    tokens should survive (KV quantization tolerance)."""
    cfg, model, params = _model("gemma-2b", key=3)
    plain = PDServer(model, params, max_seq=32, page_tokens=8)
    quant = PDServer(model, params, max_seq=32, page_tokens=8,
                     quantize_bits=8)
    prompts = np.asarray([[4, 8, 15, 9]], np.int32)
    t1, _ = plain.serve(prompts, n_steps=3)
    t2, _ = quant.serve(prompts, n_steps=3)
    # on a single device the transfer is identity; quantization is a no-op
    # only if the plan short-circuits — so just assert it runs + shape
    assert t1.shape == t2.shape
