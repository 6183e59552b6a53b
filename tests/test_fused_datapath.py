"""ISSUE 7 — the compiled flush: fused launches, batched inline codec,
device-resident rings.

Three contracts under test:

  * codec — `pack_inline_batch` / `unpack_inline_batch` and the traced
    (xp=jnp) encoders are bit-exact against the scalar codec across
    dtypes, shapes, the same-object broadcast path and ragged fallbacks;
  * launches — a flush of N WRITE WRs is exactly ONE fused device launch
    (`fused/launches` registry delta), an inline SEND flush is ZERO (the
    zero-copy host path has nothing to launch), and a device-ring CQ
    publishes each flush in one donated `fused/ring_launches` produce;
  * rings — the device-resident ring is bit-exact with the host ring
    across wraparound laps, bounded consumes and credit refreshes.

Plus kernel-level ops-vs-ref checks (tests/test_kernels.py idiom) and a
subprocess smoke test proving the fused path imports and runs under
JAX_PLATFORMS=cpu.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # offline rig: sampled fallback
    from _hyp import given, settings, st

from repro import verbs
from repro.core.notification import Ring
from repro.obs import metrics
from repro.verbs import wqe

_DTYPES = [np.float32, np.int32, np.int64, np.uint8, np.float64]


def _fused_counter(name="launches"):
    return metrics.get_registry().scope("fused").counter(name)


# -- inline codec ------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(st.integers(1, 12), st.integers(0, len(_DTYPES) - 1),
       st.integers(1, 8))
def test_pack_inline_batch_bit_exact(n, di, k):
    """Homogeneous runs (the batched fast path) must produce rows
    bit-identical to per-element pack_inline, and the batched unpack
    must invert them exactly."""
    dtype = _DTYPES[di]
    rng = np.random.default_rng(n * 31 + di * 7 + k)
    payloads = [rng.integers(0, 100, k).astype(dtype) for _ in range(n)]
    rows, nbs, dcs = wqe.pack_inline_batch(payloads)
    block = wqe.unpack_inline_batch(rows, int(nbs[0]), int(dcs[0]))
    for i, p in enumerate(payloads):
        row, nb, dc = wqe.pack_inline(p)
        np.testing.assert_array_equal(rows[i], row)
        assert (int(nbs[i]), int(dcs[i])) == (nb, dc)
        np.testing.assert_array_equal(
            wqe.unpack_inline(rows[i], nb, dc), p)
        np.testing.assert_array_equal(block[i], p)


def test_pack_inline_batch_same_object_broadcast():
    """One payload OBJECT posted n times rides the zero-copy broadcast
    path — still bit-exact with per-element packing."""
    p = np.arange(5, dtype=np.int32)
    rows, nbs, dcs = wqe.pack_inline_batch([p] * 7)
    row, nb, dc = wqe.pack_inline(p)
    assert rows.shape == (7, wqe.DESCRIPTOR_WIDTH)
    for i in range(7):
        np.testing.assert_array_equal(rows[i], row)
    assert nbs.tolist() == [nb] * 7 and dcs.tolist() == [dc] * 7
    # rows may be a read-only broadcast view; unpack must still copy out
    np.testing.assert_array_equal(
        wqe.unpack_inline_batch(rows, nb, dc)[3], p)


def test_pack_inline_batch_ragged_and_mixed_fallback():
    """Mixed dtypes / ragged shapes fall back to per-element packing and
    raise exactly where pack_inline would."""
    mixed = [np.arange(3, dtype=np.int32), np.arange(5, dtype=np.float64),
             np.arange(2, dtype=np.uint8)]
    rows, nbs, dcs = wqe.pack_inline_batch(mixed)
    for i, p in enumerate(mixed):
        row, nb, dc = wqe.pack_inline(p)
        np.testing.assert_array_equal(rows[i], row)
        np.testing.assert_array_equal(
            wqe.unpack_inline(rows[i], int(nbs[i]), int(dcs[i])), p)
    with pytest.raises(ValueError):
        wqe.pack_inline_batch([np.arange(3, dtype=np.int32),
                               np.zeros(100, np.int64)])   # over budget


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 24))
def test_traced_codec_matches_host(n):
    """The xp=jnp encoders (int32 wire words under the x64=off pin) must
    agree valuewise with the host int64 codec for in-range fields."""
    jnp = pytest.importorskip("jax.numpy")
    rng = np.random.default_rng(n)
    ops = rng.integers(0x10, 0x13, n)
    ids = rng.integers(0, 1 << 20, n)
    keys = rng.integers(0, 1 << 16, n)
    lens = rng.integers(0, 64, n)
    host = wqe.encode_wqe_batch(ops, wr_ids=ids, rkeys=keys, lkeys=keys,
                                remote_offsets=lens, lengths=lens)
    dev = wqe.encode_wqe_batch(ops, wr_ids=ids, rkeys=keys, lkeys=keys,
                               remote_offsets=lens, lengths=lens, xp=jnp)
    np.testing.assert_array_equal(host, np.asarray(dev).astype(np.int64))
    host_c = wqe.encode_cqe_batch(ops, ids, ops * 0, lens)
    dev_c = wqe.encode_cqe_batch(ops, ids, ops * 0, lens, xp=jnp)
    np.testing.assert_array_equal(host_c,
                                  np.asarray(dev_c).astype(np.int64))
    hd = wqe.decode_cqe_batch(host_c)
    dd = wqe.decode_cqe_batch(dev_c, xp=jnp)
    for k in hd:
        np.testing.assert_array_equal(hd[k],
                                      np.asarray(dd[k]).astype(np.int64))


# -- device-resident ring vs host ring ---------------------------------------
@settings(max_examples=10, deadline=None)
@given(st.integers(3, 11), st.integers(1, 8),
       st.lists(st.integers(-3, 7), min_size=1, max_size=30))
def test_device_ring_bit_exact_with_host(capacity, publish_every, ops):
    """Random produce/consume interleavings across wraparound laps: the
    device ring's drained descriptors, slot bytes, flags and bookkeeping
    must match the host vectorized ring exactly."""
    dev = Ring(capacity, publish_every=publish_every, device=True)
    host = Ring(capacity, publish_every=publish_every, vectorized=True)
    seq = 0
    for op in ops:
        if op <= 0:
            a = dev.consume(None if op == 0 else -op)
            b = host.consume(None if op == 0 else -op)
            np.testing.assert_array_equal(a, b)
        else:
            n = min(op, host.capacity - (host.head - host._published_tail))
            if n <= 0:
                continue
            batch = np.arange(seq * 8, (seq + n) * 8,
                              dtype=np.int64).reshape(n, 8)
            seq += n
            assert dev.produce(batch) == host.produce(batch) == n
    np.testing.assert_array_equal(dev.consume(), host.consume())
    assert (dev.head, dev.tail, dev._published_tail, dev._since_publish) \
        == (host.head, host.tail, host._published_tail,
            host._since_publish)
    np.testing.assert_array_equal(dev.slots_view(), host.slots_view())
    np.testing.assert_array_equal(dev.flags_view(), host.flags_view())


def test_device_ring_rejects_scalar_oracle():
    """The oracle never compiles — device=True with vectorized=False is
    a contract violation, not a silent fallback."""
    with pytest.raises(ValueError):
        Ring(8, device=True, vectorized=False)


def test_device_ring_cq_end_to_end():
    """A device-ring recv CQ behind a loopback SEND flush: completions
    match a host-ring CQ bit-for-bit and each flush's CQE block lands in
    donated ring produces (fused/ring_launches moves, host memcpy path
    does not)."""
    wcs = {}
    for device_ring in (False, True):
        pd = verbs.ProtectionDomain()
        t = verbs.LoopbackTransport()
        recv_cq = verbs.CompletionQueue(64, 8, device_ring=device_ring)
        c = verbs.QueuePair(pd, verbs.CompletionQueue(64, 8))
        s = verbs.QueuePair(pd, verbs.CompletionQueue(64, 8), recv_cq,
                            max_recv_wr=32)
        verbs.connect(c, s, t)
        for i in range(8):
            s.post_recv(verbs.RecvWR(wr_id=i))
        payload = np.arange(4, dtype=np.int64)
        rl = _fused_counter("ring_launches").value
        c.post_send([verbs.SendWR(wr_id=i, payload=payload,
                                  signaled=False) for i in range(8)])
        c.flush()
        moved = _fused_counter("ring_launches").value - rl
        assert (moved > 0) == device_ring
        wcs[device_ring] = recv_cq.poll()
    assert len(wcs[False]) == len(wcs[True]) == 8
    for a, b in zip(wcs[False], wcs[True]):
        assert (a.wr_id, a.opcode, a.status, a.length) == \
               (b.wr_id, b.opcode, b.status, b.length)
        np.testing.assert_array_equal(a.data, b.data)


# -- launches-per-flush regression -------------------------------------------
@pytest.mark.parametrize("n", [1, 5, 64])
def test_write_flush_is_one_fused_launch(n):
    """The compiled-flush contract: a flush of N WRITE WRs costs exactly
    ONE fused device launch, independent of N."""
    pair = verbs.VerbsPair(depth=n + 16, max_wr=n + 8)
    dst = pair.pd.reg_mr("dst", np.zeros((n, 4), np.float32))
    wrs = [verbs.SendWR(wr_id=i, opcode=verbs.IBV_WR_RDMA_WRITE,
                        remote_key=dst.rkey, remote_offsets=[i],
                        payload=np.full((1, 4), float(i + 1), np.float32),
                        signaled=False) for i in range(n)]
    pair.client.post_send(wrs)          # warm the jit cache
    pair.client.flush()
    pair.client.post_send(wrs)
    before = _fused_counter().value
    pair.client.flush()
    assert _fused_counter().value - before == 1
    got = pair.pd.mr_array(dst)
    np.testing.assert_allclose(
        got, np.arange(1, n + 1, dtype=np.float32)[:, None].repeat(4, 1))


def test_inline_send_flush_is_launch_free():
    """Inline SENDs ride host cachelines end to end: header + payload
    are staged and delivered zero-copy, so the fused-launch counter must
    NOT move across the flush."""
    n = 32
    srq = verbs.SharedReceiveQueue(max_wr=n + 8)
    pair = verbs.VerbsPair(depth=n + 16, max_wr=n + 8, srq=srq)
    srq.post_recv([verbs.RecvWR(wr_id=i) for i in range(n)])
    payload = np.arange(4, dtype=np.int64)
    pair.client.post_send([verbs.SendWR(wr_id=i, payload=payload,
                                        signaled=False)
                           for i in range(n)])
    before = _fused_counter().value
    pair.client.flush()
    assert _fused_counter().value - before == 0
    wcs = pair.server_recv_cq.poll()
    assert len(wcs) == n
    for wc in wcs:
        np.testing.assert_array_equal(wc.data, payload)


# -- kernel ops vs refs (tests/test_kernels.py idiom) ------------------------
@pytest.mark.parametrize("m", [1, 3, 8, 13])
def test_wr_scatter_ops_match_ref(m):
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.wr_scatter import ops, ref
    rng = np.random.default_rng(m)
    base = rng.standard_normal((16, 4)).astype(np.float32)
    offs = rng.choice(16, size=m, replace=False)
    vals = rng.standard_normal((m, 4)).astype(np.float32)
    before = _fused_counter().value
    got = ops.scatter_records(jnp.asarray(base), offs, vals)
    assert _fused_counter().value - before == 1
    want = ref.reference(jnp.asarray(base), vals, offs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [1, 2, 7])
def test_wr_gather_ops_match_ref(m):
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.wr_scatter import ops, ref
    rng = np.random.default_rng(100 + m)
    region = rng.standard_normal((16, 4)).astype(np.float32)
    offs = rng.choice(16, size=m, replace=False)
    got = np.asarray(ops.gather_records(jnp.asarray(region), offs, 4))[:m]
    idx = offs[:, None] * 4 + np.arange(4)
    want = np.asarray(ref.reference_gather(jnp.asarray(region),
                                           idx.astype(np.int32)))
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("m", [1, 3, 5])
def test_wr_gather_page_region_matches_ref(m):
    """Whole page records of a multi-dim region, gathered by row index,
    equal the flat-element reference gather."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.wr_scatter import ops, ref
    rng = np.random.default_rng(200 + m)
    region = rng.standard_normal((9, 3, 4, 2, 8)).astype(np.float32)
    length = 3 * 4 * 2 * 8
    offs = rng.choice(9, size=m, replace=False)
    got = np.asarray(ops.gather_records(jnp.asarray(region), offs,
                                        length))[:m]
    idx = offs[:, None] * length + np.arange(length)
    want = np.asarray(ref.reference_gather(jnp.asarray(region),
                                           idx.astype(np.int32)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape", [(12, 3, 4, 2, 8), (12, 4, 16)])
def test_wr_scatter_kernel_page_records(shape):
    """The Pallas kernel (interpret mode) lands whole multi-dim records,
    one block of their own trailing dims each, like the reference."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.wr_scatter import ref
    from repro.kernels.wr_scatter.wr_scatter import wr_scatter
    rng = np.random.default_rng(len(shape))
    region = rng.standard_normal(shape).astype(np.float32)
    offs = np.array([7, 0, 11], np.int32)
    vals = rng.standard_normal((3,) + shape[1:]).astype(np.float32)
    got = wr_scatter(jnp.asarray(region), jnp.asarray(vals), offs,
                     interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.reference(jnp.asarray(region),
                                                  vals, offs)))


def test_wr_scatter_route_is_a_fixed_rule(monkeypatch):
    """On TPU, records of >= 2 dims take the Pallas kernel and 1-D
    records the XLA scatter; off TPU everything takes XLA — decided by
    backend and record rank, never by a failure at run time."""
    from repro.kernels.wr_scatter import ops
    monkeypatch.setattr(ops, "_ON_TPU", True)
    assert ops._use_pallas(np.zeros((4, 2, 8)))
    assert not ops._use_pallas(np.zeros((4, 16)))
    monkeypatch.setattr(ops, "_ON_TPU", False)
    assert not ops._use_pallas(np.zeros((4, 2, 8)))
    with pytest.raises(ValueError):
        from repro.kernels.wr_scatter.wr_scatter import wr_scatter
        wr_scatter(np.zeros((4, 16)), np.zeros((1, 16)), [0],
                   interpret=True)


def test_desc_ring_ops_roundtrip_across_laps():
    """Kernel-level: produced descriptor batches come back bit-exact and
    in order through multiple wraparound laps of the device slots."""
    from repro.kernels.desc_ring import ops
    cap, width = 6, 8
    slots, flags = ops.alloc(cap, width)
    head = tail = 0
    for lap in range(3):
        batch = np.arange(lap * 100, lap * 100 + 4 * width,
                          dtype=np.int64).reshape(4, width)
        slots, flags = ops.produce(slots, flags, head, batch)
        head += 4
        out = ops.consume(slots, flags, tail, limit=cap)
        tail += out.shape[0]
        np.testing.assert_array_equal(out, batch)
    assert head == tail == 12


# -- the fused path under a pinned CPU backend ------------------------------
@pytest.mark.slow
def test_fused_path_runs_under_cpu_subprocess():
    """Fresh interpreter, JAX_PLATFORMS=cpu: the fused WRITE path must
    import, run one launch per flush, and land the
    right bytes — proof the jit entry points don't depend on ambient
    backend state from this process."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = (
        "import numpy as np\n"
        "from repro import verbs\n"
        "from repro.obs import metrics\n"
        "pair = verbs.VerbsPair(depth=32, max_wr=16)\n"
        "dst = pair.pd.reg_mr('dst', np.zeros((4, 4), np.float32))\n"
        "wrs = [verbs.SendWR(wr_id=i, opcode=verbs.IBV_WR_RDMA_WRITE,\n"
        "                    remote_key=dst.rkey, remote_offsets=[i],\n"
        "                    payload=np.full((1, 4), i + 1.0, np.float32),\n"
        "                    signaled=False) for i in range(4)]\n"
        "pair.client.post_send(wrs); pair.client.flush()\n"
        "pair.client.post_send(wrs)\n"
        "c = metrics.get_registry().scope('fused').counter('launches')\n"
        "b = c.value\n"
        "pair.client.flush()\n"
        "assert c.value - b == 1, (c.value, b)\n"
        "got = pair.pd.mr_array(dst)\n"
        "assert np.allclose(got[:, 0], [1, 2, 3, 4]), got\n"
        "import jax\n"
        "print('FUSED_OK', jax.default_backend())\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(repo, "src")
           + os.pathsep + os.environ.get("PYTHONPATH", "")}
    res = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert "FUSED_OK cpu" in res.stdout
