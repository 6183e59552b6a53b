"""ISSUE 10: paged KV-as-MRs, bucketed prefill, RDMA page migration and
the routed serving cluster — plus the DCQCN reaction-point properties.

The load-bearing invariants:
  * paged decode (slot -> page-table indirection over MR-backed pages)
    is bit-exact with dense decode and with the sequential reference;
  * bucketed prefill compiles O(log max_seq) variants, not one per
    prompt length, without changing a single output token;
  * a page migration is ONE doorbell and ONE fused gather launch per
    cache-leaf run (plus one stacked scatter landing it);
  * the cluster (router + prefill pods + decode pods) reproduces the
    single-pod oracle exactly — including when a decode pod is killed
    mid-run by a seeded FaultModel trigger;
  * engine bookkeeping is bounded: finished requests leave the live
    dicts and pages return to the pool.
"""
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # offline rig: sampled fallback
    from _hyp import given, settings, st

from repro import verbs
from repro.configs.base import get_config, reduced
from repro.models.registry import build_model
from repro.obs import metrics
from repro.serve.engine import ServeEngine
from repro.serve.paged import (PagePool, bucket_len, bucketable, pageable)
from repro.serve.pd_disagg import PrefillPod
from repro.serve.router import Router
from repro.verbs.ratectl import RateController, RouteState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DECODE_GIDS = ["pod2/dev0", "pod3/dev0"]
PREFILL_GIDS = ["pod0/dev0", "pod1/dev0"]


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced(get_config("gemma-2b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _model(arch, key=0):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(key))


def _reference_generate(model, params, prompt, n_new, max_seq):
    """Greedy generation through prefill+decode (the trusted path)."""
    from repro.serve.kvcache import pad_caches
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


def _cluster(fabric, model, params, max_seq=64, page_tokens=8):
    engines = [ServeEngine(model, params, max_batch=2, max_seq=max_seq,
                           fabric=fabric, gid=g, service=f"serve/{g}",
                           page_tokens=page_tokens) for g in DECODE_GIDS]
    pods = [PrefillPod(model, params, fabric=fabric, gid=g,
                       decode_gids=DECODE_GIDS, max_seq=max_seq,
                       page_tokens=page_tokens) for g in PREFILL_GIDS]
    router = Router(fabric)
    for e in engines:
        router.add_decode(e)
    for p in pods:
        router.add_prefill(p)
    return router, engines, pods


# -- paging / bucketing eligibility -------------------------------------

def test_bucket_len():
    assert [bucket_len(n, 64) for n in (1, 2, 3, 5, 8, 9, 33, 64)] == \
        [1, 2, 4, 8, 8, 16, 64, 64]
    assert bucket_len(100, 64) == 64        # capped at max_len
    with pytest.raises(ValueError):
        bucket_len(0, 64)


def test_eligibility_probing(gemma):
    model, _ = gemma
    assert pageable(model) and bucketable(model)
    mamba, _ = _model("mamba2-780m", key=1)
    assert not pageable(mamba)              # state caches, not seq pages
    moe, _ = _model("granite-moe-1b-a400m", key=1)
    # MoE: pages are fine, bucketing is not (capacity depends on tokens)
    assert pageable(moe) and not bucketable(moe)
    rg, _ = _model("recurrentgemma-2b", key=1)
    assert not pageable(rg)                 # hybrid window/rec stack


def test_unpageable_model_falls_back_dense():
    model, params = _model("mamba2-780m", key=1)
    eng = ServeEngine(model, params, max_batch=2, max_seq=48)
    assert not eng.paged and not eng.bucketed and eng.pool is None
    # 5 tokens: avoids the pad_caches seq-vs-state-width ambiguity the
    # dense path inherits for state-space caches
    prompt = [5, 3, 9, 1, 2]
    rid = eng.submit(prompt, max_new_tokens=3)
    res = eng.run_until_done()
    assert res[rid] == _reference_generate(model, params, prompt, 3, 48)
    eng.close()


# -- paged decode correctness -------------------------------------------

def test_paged_matches_dense_and_reference(gemma):
    model, params = gemma
    prompts = [[5, 3, 9, 1], [7, 7, 2], [1, 2, 3, 4, 5, 6, 7, 8, 9]]
    paged = ServeEngine(model, params, max_batch=2, max_seq=64,
                        paged=True, page_tokens=8)
    dense = ServeEngine(model, params, max_batch=2, max_seq=64,
                        paged=False)
    assert paged.paged and not dense.paged
    rp = [paged.submit(p, max_new_tokens=6) for p in prompts]
    rd = [dense.submit(p, max_new_tokens=6) for p in prompts]
    resp, resd = paged.run_until_done(), dense.run_until_done()
    for prompt, a, b in zip(prompts, rp, rd):
        exp = _reference_generate(model, params, prompt, 6, 64)
        assert resp[a] == exp, (prompt, resp[a], exp)
        assert resd[b] == exp
    paged.close()
    dense.close()


def test_engine_dicts_bounded_and_pages_returned(gemma):
    """Retention fix: requests/pinned_prompts empty after each wave,
    every page back in the pool, the table all-null."""
    model, params = gemma
    eng = ServeEngine(model, params, max_batch=2, max_seq=64,
                      page_tokens=8)
    for wave in range(3):
        rids = [eng.submit([1 + wave, 2, 3 + i], max_new_tokens=3)
                for i in range(4)]
        res = eng.run_until_done()
        assert all(len(res[r]) == 3 for r in rids)
        assert not eng.requests and not eng.pinned_prompts
    assert len(eng.pool._free) == eng.pool.n_pages - 1   # all but null
    assert (eng.pool.table == 0).all()
    assert eng.pool.pages_allocated == eng.pool.pages_freed > 0
    eng.close()
    assert not eng._finished


def test_bucketed_prefill_compile_count(gemma):
    """11 distinct prompt lengths, O(log max_seq) prefill compiles,
    outputs bit-exact against unpadded reference prefill."""
    model, params = gemma
    eng = ServeEngine(model, params, max_batch=2, max_seq=64,
                      page_tokens=8)
    assert eng.bucketed
    lens = list(range(1, 12))
    rids = [eng.submit(list(range(1, n + 1)), max_new_tokens=2)
            for n in lens]
    res = eng.run_until_done()
    assert eng.prefill_compiles <= math.ceil(math.log2(64)) + 1
    assert eng.prefill_compiles < len(set(lens))
    for n, r in zip(lens, rids):
        exp = _reference_generate(model, params, list(range(1, n + 1)),
                                  2, 64)
        assert res[r] == exp, (n, res[r], exp)
    eng.close()


# -- page migration ------------------------------------------------------

def test_migrate_pages_one_fused_launch_per_leaf_run(gemma):
    """A 3-page migration is ONE WQE chain (one doorbell, one desc-fetch
    DMA) and exactly one gather + one scatter launch per cache-leaf run
    — and the pages land bit-exact in the decode pool's MRs."""
    model, params = gemma
    fabric = verbs.Fabric(pods=2)
    eng = ServeEngine(model, params, max_batch=2, max_seq=64,
                      fabric=fabric, gid="pod1/dev0",
                      service="serve/pod1/dev0", page_tokens=8)
    pod = PrefillPod(model, params, fabric=fabric, gid="pod0/dev0",
                     decode_gids=["pod1/dev0"], max_seq=64, page_tokens=8)
    prompt = np.arange(1, 18, dtype=np.int32)        # 17 tokens, 3 pages
    logits, caches = pod._run_prefill(prompt)
    first = int(jnp.argmax(logits[0, -1]))
    k = pod.pool.pages_for(17)
    assert k == 3
    src_ids = pod.pool.alloc(k)
    pod.pool.fill(src_ids, caches)
    lease = eng.reserve(0, 17, 4, first)
    runs = [(mr, src_ids, rkey, dst)
            for mr, (rkey, dst) in zip(pod.pool.mrs, lease)]
    launches0 = metrics.get_registry().snapshot().get("fused/launches", 0)
    d0, f0 = pod.kv.ep.qp.doorbell_writes, pod.kv.ep.qp.desc_fetch_dmas
    pod.kv.migrate_pages(runs)
    assert pod.kv.ep.qp.doorbell_writes - d0 == 1
    assert pod.kv.ep.qp.desc_fetch_dmas - f0 == 1
    launches1 = metrics.get_registry().snapshot().get("fused/launches", 0)
    n_leaf_runs = len(pod.pool.mrs)
    assert launches1 - launches0 == 2 * n_leaf_runs
    assert pod.kv.pages_migrated == k * n_leaf_runs
    for i, (src_r, dst_r) in enumerate(zip(pod.pool.regions(),
                                           eng.pool.regions())):
        np.testing.assert_array_equal(
            np.asarray(src_r)[src_ids],
            np.asarray(dst_r)[np.asarray(lease[i][1])])
    pod.close()
    eng.close()


class _NumpyWithoutDeviceArrays:
    """numpy for the datapath modules under test, refusing any jax array
    it is asked to convert. `jax.transfer_guard_device_to_host` only
    fires where a copy really happens, and CPU arrays convert to numpy
    without one, so on the CPU the tests guard the conversion itself."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def _refuse(x):
        if isinstance(x, jax.Array):
            raise AssertionError(
                f"device array {x.shape} {x.dtype} copied to the host")
        if isinstance(x, (list, tuple)):
            for e in x:
                _NumpyWithoutDeviceArrays._refuse(e)

    def asarray(self, a, *args, **kw):
        self._refuse(a)
        return np.asarray(a, *args, **kw)

    def array(self, a, *args, **kw):
        self._refuse(a)
        return np.array(a, *args, **kw)

    def concatenate(self, arrays, *args, **kw):
        self._refuse(arrays)
        return np.concatenate(arrays, *args, **kw)


def _forbid_device_to_host(monkeypatch):
    from repro.core import kvtransfer, offload_engine
    from repro.kernels.wr_scatter import ops
    from repro.verbs import transport
    guarded = _NumpyWithoutDeviceArrays()
    for mod in (transport, offload_engine, ops, kvtransfer):
        monkeypatch.setattr(mod, "np", guarded)


@pytest.mark.parametrize("k", [1, 3, 5, 8, 17])
def test_migrate_pages_keeps_page_bytes_on_device(gemma, monkeypatch, k):
    """A k-page migration lands bit-exact in the decode pool's MRs with
    no device->host copy: each leaf run's gathered block is the DMA's
    buffer (one device-path sub-run per leaf, none on the host path),
    whether or not k is a power of two."""
    model, params = gemma
    fabric = verbs.Fabric(pods=2)
    eng = ServeEngine(model, params, max_batch=1, max_seq=256,
                      fabric=fabric, gid="pod1/dev0",
                      service="serve/pod1/dev0", page_tokens=8)
    pod = PrefillPod(model, params, fabric=fabric, gid="pod0/dev0",
                     decode_gids=["pod1/dev0"], max_seq=256, page_tokens=8)
    rng = np.random.default_rng(k)
    pod.pool.rebind([jnp.asarray(rng.standard_normal(r.shape), r.dtype)
                     for r in pod.pool.regions()])
    src_ids = pod.pool.alloc(k)
    dst_ids = eng.pool.alloc(k)
    runs = [(mr, src_ids, rkey, dst) for mr, (rkey, dst)
            in zip(pod.pool.mrs, eng.pool.lease(dst_ids))]
    want = [np.asarray(r)[src_ids] for r in pod.pool.regions()]
    fused = metrics.get_registry().scope("fused")
    dev0 = fused.counter("device_write_runs").value
    host0 = fused.counter("host_write_runs").value
    nbytes0 = fused.counter("device_write_bytes").value
    _forbid_device_to_host(monkeypatch)
    with jax.transfer_guard_device_to_host("disallow"):
        pod.kv.migrate_pages(runs)
    monkeypatch.undo()
    n_leaf_runs = len(pod.pool.mrs)
    assert fused.counter("device_write_runs").value - dev0 == n_leaf_runs
    assert fused.counter("host_write_runs").value == host0
    assert fused.counter("device_write_bytes").value - nbytes0 == \
        sum(w.nbytes for w in want)
    for w, dst_r in zip(want, eng.pool.regions()):
        np.testing.assert_array_equal(np.asarray(dst_r)[dst_ids], w)
    # landed blocks are released, not pinned by the decode QP's DMA queue
    assert all(d.buf is None for d in pod.kv.ep.peer.qp.ctx._dma_queue)
    pod.close()
    eng.close()


@pytest.mark.slow
def test_migrate_pages_across_devices_stays_on_device():
    """On a physically backed two-device grid (each pod's pool on its own
    device, `Fabric.device_of`), every leaf run still takes the device
    path: its block moves to the decode pool's device and lands there
    bit-exact."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, jax.numpy as jnp, numpy as np
        from repro import verbs
        from repro.configs.base import get_config, reduced
        from repro.models.registry import build_model
        from repro.obs import metrics
        from repro.serve.engine import ServeEngine
        from repro.serve.pd_disagg import PrefillPod

        model = build_model(reduced(get_config("gemma-2b")))
        params = model.init(jax.random.PRNGKey(0))
        fabric = verbs.Fabric(pods=2)
        eng = ServeEngine(model, params, max_batch=1, max_seq=64,
                          fabric=fabric, gid="pod1/dev0",
                          service="serve/pod1/dev0", page_tokens=8)
        pod = PrefillPod(model, params, fabric=fabric, gid="pod0/dev0",
                         decode_gids=["pod1/dev0"], max_seq=64,
                         page_tokens=8)
        src_dev, dst_dev = jax.devices()
        rng = np.random.default_rng(0)
        pod.pool.rebind([jax.device_put(
            jnp.asarray(rng.standard_normal(r.shape), r.dtype), src_dev)
            for r in pod.pool.regions()])
        src_ids, dst_ids = pod.pool.alloc(5), eng.pool.alloc(5)
        runs = [(mr, src_ids, rkey, dst) for mr, (rkey, dst)
                in zip(pod.pool.mrs, eng.pool.lease(dst_ids))]
        want = [np.asarray(r)[src_ids] for r in pod.pool.regions()]
        fused = metrics.get_registry().scope("fused")
        pod.kv.migrate_pages(runs)
        assert fused.counter("device_write_runs").value == len(runs)
        assert fused.counter("host_write_runs").value == 0
        for w, r in zip(want, eng.pool.regions()):
            assert r.devices() == {dst_dev}, r.devices()
            np.testing.assert_array_equal(np.asarray(r)[dst_ids], w)
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0 and "OK" in r.stdout, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"


def test_migrated_pages_survive_source_reuse(gemma):
    """Nothing syncs between a migration's gather and its landing, so a
    source page freed and refilled with another request's caches right
    after `migrate_pages` returns must not reach the decode pool: the
    pool still holds the first request's pages, and decoding from them
    reproduces the single-pod oracle."""
    model, params = gemma
    fabric = verbs.Fabric(pods=2)
    eng = ServeEngine(model, params, max_batch=2, max_seq=64,
                      fabric=fabric, gid="pod1/dev0",
                      service="serve/pod1/dev0", page_tokens=8)
    pod = PrefillPod(model, params, fabric=fabric, gid="pod0/dev0",
                     decode_gids=["pod1/dev0"], max_seq=64, page_tokens=8)
    router = Router(fabric)
    router.add_decode(eng)
    router.add_prefill(pod)
    _, other = pod._run_prefill(np.arange(40, 63, dtype=np.int32))
    migrate = pod.kv.migrate_pages
    checked = []

    def migrate_then_reuse(runs, **kw):
        gid = migrate(runs, **kw)
        _, src_ids, _, _ = runs[0]
        want = [np.asarray(r)[src_ids] for r in pod.pool.regions()]
        pod.pool.free(src_ids)
        again = pod.pool.alloc(len(src_ids))
        assert sorted(again) == sorted(src_ids)
        pod.pool.fill(src_ids, other)
        for w, r, run in zip(want, eng.pool.regions(), runs):
            np.testing.assert_array_equal(np.asarray(r)[run[3]], w)
        checked.append(len(src_ids))
        return gid
    pod.kv.migrate_pages = migrate_then_reuse
    prompts = PROMPTS[:3] + [list(range(3, 23))]      # 1 and 3 pages
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]
    res = router.run_until_done()
    assert checked == [1, 1, 1, 3]
    exp = _oracle(model, params, prompts)
    for r, e in zip(rids, exp):
        assert res[r] == e, (r, res[r], e)
    router.close()


# -- the cluster ---------------------------------------------------------

PROMPTS = [[5, 3, 9, 1], [7, 7, 2], [1, 2, 3, 4, 5], [9, 8, 7],
           [4, 8, 15, 16], [23, 42, 3]]


def _oracle(model, params, prompts):
    """Single-pod engine on the scalar verbs datapath: the bit-exactness
    oracle the cluster must reproduce."""
    eng = ServeEngine(model, params, max_batch=2, max_seq=64,
                      vectorized=False, page_tokens=8)
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    res = eng.run_until_done()
    eng.close()
    return [res[r] for r in rids]


def test_cluster_bit_exact_vs_single_pod(gemma):
    model, params = gemma
    fabric = verbs.Fabric(pods=4)
    router, engines, pods = _cluster(fabric, model, params)
    rids = [router.submit(p, max_new_tokens=6) for p in PROMPTS]
    res = router.run_until_done()
    exp = _oracle(model, params, PROMPTS)
    for r, e in zip(rids, exp):
        assert res[r] == e, (r, res[r], e)
    # both decode pods took work, every migration was RDMA pages
    assert all(len(e._finished) == 0 for e in engines)   # drained by router
    assert sum(p.kv.pages_migrated for p in pods) > 0
    assert router.failovers == 0
    router.close()
    assert not fabric.qps and not fabric.routes and not fabric._listeners


def test_cluster_survives_decode_pod_kill(gemma):
    """Seeded FaultModel kill of one decode pod mid-run: its requests
    re-route through the survivor and the final tokens are STILL
    bit-exact against the single-pod oracle."""
    model, params = gemma
    faults = verbs.FaultModel(seed=7).kill_after("pod3/dev0", 2)
    fabric = verbs.Fabric(pods=4, faults=faults)
    router, engines, pods = _cluster(fabric, model, params)
    rids = [router.submit(p, max_new_tokens=6) for p in PROMPTS]
    res = router.run_until_done()
    assert not fabric.alive("pod3/dev0")     # the kill landed mid-run
    assert faults.kills_triggered == 1
    exp = _oracle(model, params, PROMPTS)
    for r, e in zip(rids, exp):
        assert res[r] == e, (r, res[r], e)
    assert router.failovers >= 1             # orphaned work re-routed
    router.close()


# -- DCQCN reaction-point properties (satellite 3) -----------------------

@settings(max_examples=20)
@given(marks=st.lists(st.integers(0, 1), min_size=0, max_size=64))
def test_ratectl_rate_envelope(marks):
    """ANY ECN mark schedule keeps min_rate <= rate <= line_rate and
    alpha in [0, 1]; a drained link recovers additively to line rate."""
    ctl = RateController(verbs.Fabric())
    rs = RouteState(ctl, "pod0/dev0", "pod0/dev1")
    for m in marks:
        rs.react(ctl, bool(m))
        assert ctl.min_rate <= rs.rate <= ctl.line_rate
        assert 0.0 <= rs.alpha <= 1.0
    inc0 = rs.rate_increases
    for _ in range(64):                      # marks stop: drained link
        rs.react(ctl, False)
        assert ctl.min_rate <= rs.rate <= ctl.line_rate
    assert rs.rate == ctl.line_rate
    assert rs.alpha < 0.05                   # congestion estimate decayed
    # recovery is additive: it took >= (line-min)/ai_increment increments
    if marks and any(marks):
        assert rs.rate_increases > inc0


@settings(max_examples=8)
@given(data=st.data())
def test_ratectl_saturating_marks_floor_at_min_rate(data):
    """Sustained marking saturates at min_rate, never below, and alpha
    converges toward 1 — the DCQCN fixed point."""
    n = data.draw(st.integers(16, 200))
    ctl = RateController(verbs.Fabric())
    rs = RouteState(ctl, "pod1/dev0", "pod0/dev0")
    for _ in range(n):
        rs.react(ctl, True)
    assert rs.rate >= ctl.min_rate
    if n >= 100:
        assert rs.rate == ctl.min_rate
    assert 0.0 <= rs.alpha <= 1.0
