"""ISSUE 3 — vectorized datapath vs the element-at-a-time scalar oracle.

Every batch-wise fast path (slice-based ring produce/consume, run-grouped
dispatch, fused WRITE scatters, per-CQ CQE blocks) must be *bit-exact*
against the retained `vectorized=False` implementation across random
chain lengths, wrap positions, opcode mixes, lap-flag toggles and
mid-chain RNR stalls — plus the launch/DMA counter contracts the
benchmarks report."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # offline rig: sampled fallback
    from _hyp import given, settings, st

from repro import verbs
from repro.core.notification import DoorbellQueue, Ring
from repro.obs import metrics
from repro.verbs import wqe


# -- codec -------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(st.integers(1, 40))
def test_wqe_cqe_batch_codecs_match_scalar(n):
    rng = np.random.default_rng(n)
    ops = rng.integers(0x10, 0x13, n)
    wr_ids = rng.integers(0, 1 << 20, n)
    keys = rng.integers(0, 1 << 16, n)
    lens = rng.integers(0, 64, n)
    flags = rng.integers(0, 8, n)
    dcodes = rng.integers(0, 6, n)
    batch = wqe.encode_wqe_batch(ops, wr_ids=wr_ids, rkeys=keys, lkeys=keys,
                                 remote_offsets=lens, lengths=lens,
                                 flags=flags, dtype_codes=dcodes)
    for i in range(n):
        np.testing.assert_array_equal(batch[i], wqe.encode_wqe(
            int(ops[i]), wr_id=int(wr_ids[i]), rkey=int(keys[i]),
            lkey=int(keys[i]), remote_offset=int(lens[i]),
            length=int(lens[i]), flags=int(flags[i]),
            dtype_code=int(dcodes[i])))
    cqes = wqe.encode_cqe_batch(ops, wr_ids, keys, lens, flags, dcodes)
    dec = wqe.decode_cqe_batch(cqes)
    for i in range(n):
        np.testing.assert_array_equal(cqes[i], wqe.encode_cqe(
            int(ops[i]), int(wr_ids[i]), int(keys[i]), int(lens[i]),
            int(flags[i]), int(dcodes[i])))
        scalar = wqe.cqe_fields(cqes[i])
        for k, v in scalar.items():
            assert int(dec[k][i]) == v, k


# -- ring: slice-based produce/consume vs the row-loop oracle ----------------
@settings(max_examples=30, deadline=None)
@given(st.integers(3, 17), st.integers(1, 12),
       st.lists(st.integers(-3, 9), min_size=1, max_size=40))
def test_ring_vectorized_bit_exact(capacity, publish_every, ops):
    """Random produce/consume interleavings across many wraparound laps:
    slots, flags, counters and every drained descriptor must match the
    scalar ring exactly (negative op = bounded consume, 0 = drain)."""
    rings = [Ring(capacity, publish_every=publish_every, vectorized=v)
             for v in (True, False)]
    seq = 0
    for op in ops:
        if op <= 0:
            got = [r.consume(None if op == 0 else -op) for r in rings]
            np.testing.assert_array_equal(got[0], got[1])
        else:
            # clamp to the credit the producer can SEE (post-refresh):
            # the consumer may not have published its counter yet
            r0 = rings[0]
            n = min(op, r0.capacity - (r0.head - r0._published_tail))
            if n <= 0:
                continue
            batch = np.arange(seq * 8, (seq + n) * 8,
                              dtype=np.int64).reshape(n, 8)
            seq += n
            assert rings[0].produce(batch) == rings[1].produce(batch) == n
    for a, b in zip(rings[0].consume(), rings[1].consume()):
        np.testing.assert_array_equal(a, b)
    v, s = rings
    assert (v.head, v.tail, v._published_tail, v._since_publish) == \
           (s.head, s.tail, s._published_tail, s._since_publish)
    assert (v.dma_writes, v.dma_reads) == (s.dma_writes, s.dma_reads)
    np.testing.assert_array_equal(v.slots, s.slots)
    np.testing.assert_array_equal(v.flags, s.flags)


def test_ring_empty_batch_is_noop_both_paths():
    for v in (True, False):
        ring = Ring(4, vectorized=v)
        assert ring.produce([]) == 0
        assert ring.produce(np.zeros((0, 8), np.int64)) == 0
        assert ring.dma_writes == 0 and len(ring) == 0


def test_doorbell_queue_empty_batch_is_noop():
    """Regression: np.atleast_2d([]) is a (1, 0) row — an empty batch
    must early-return 0 (no doorbell, no fetch, nothing produced at the
    wrong width) exactly like Ring.produce."""
    q = DoorbellQueue(8)
    assert q.produce([]) == 0
    assert q.produce(np.zeros((0, 8), np.int64)) == 0
    assert q.doorbell_writes == 0 and q.fetch_dmas == 0
    assert len(q.consume()) == 0


# -- dispatch: run-grouped vs element-at-a-time ------------------------------
_KINDS = ["send_inline", "send_f64", "send_u8", "send_big", "send_unsig",
          "send_mr", "write", "write_bad", "read", "write_mr",
          "write_mr_dup", "write_mr_bcast"]


def _run_chain(kinds, n_recv, use_srq, vectorized):
    """Post one mixed WQE chain and return everything observable."""
    # pin the process-wide key counter so both runs mint identical
    # lkeys/rkeys (descriptors must be comparable bit-for-bit)
    verbs.ProtectionDomain._next_key = 0x7000
    srq = verbs.SharedReceiveQueue(max_wr=256) if use_srq else None
    pair = verbs.VerbsPair(depth=1024, publish_every=8, srq=srq,
                           vectorized=vectorized)
    dst = pair.pd.reg_mr("dst", np.zeros((8, 4), np.float32))
    src = pair.pd.reg_mr("src", np.arange(32, dtype=np.float32)
                         .reshape(8, 4))
    rng = np.random.default_rng(len(kinds) * 101 + n_recv)
    recvs = [verbs.RecvWR(wr_id=100 + i) for i in range(n_recv)]
    if use_srq:
        srq.post_recv(recvs)
    else:
        for r in recvs:
            pair.server.post_recv(r)
    wrs = []
    for i, kind in enumerate(kinds):
        if kind == "send_inline":
            wrs.append(verbs.SendWR(wr_id=i, payload=np.array(
                [i, 7, i * i], np.int32)))
        elif kind == "send_f64":
            wrs.append(verbs.SendWR(wr_id=i, payload=np.array(
                [i + 0.5, -i], np.float64)))
        elif kind == "send_u8":
            wrs.append(verbs.SendWR(wr_id=i, payload=np.arange(
                1 + i % 7, dtype=np.uint8)))
        elif kind == "send_mr":
            k = int(rng.integers(1, 4))
            wrs.append(verbs.SendWR(
                wr_id=i, payload=None, mr=src,
                offsets=rng.choice(8, size=k, replace=False)))
        elif kind == "send_big":
            wrs.append(verbs.SendWR(wr_id=i, inline=False, payload=rng
                       .standard_normal(40).astype(np.float32)))
        elif kind == "send_unsig":
            wrs.append(verbs.SendWR(wr_id=i, signaled=False,
                                    payload=np.array([i], np.int64)))
        elif kind in ("write", "write_bad"):
            k = int(rng.integers(1, 4))
            offs = rng.choice(8, size=k, replace=False)
            wrs.append(verbs.SendWR(
                wr_id=i, opcode=verbs.IBV_WR_RDMA_WRITE,
                remote_key=0xDEAD if kind == "write_bad" else dst.rkey,
                remote_offsets=offs,
                payload=rng.standard_normal((k, 4)).astype(np.float32)))
        elif kind in ("write_mr", "write_mr_dup", "write_mr_bcast"):
            # MR-sourced: a distinct-offset WR, one that lands on the
            # same destination record as every other `_dup`, and one
            # whose single source row broadcasts over two records
            k = int(rng.integers(1, 4)) if kind == "write_mr" else 1
            if kind == "write_mr":
                dst_offs = rng.choice(8, size=k, replace=False)
            elif kind == "write_mr_dup":
                dst_offs = np.asarray([7])
            else:
                dst_offs = rng.choice(8, size=2, replace=False)
            wrs.append(verbs.SendWR(
                wr_id=i, opcode=verbs.IBV_WR_RDMA_WRITE,
                remote_key=dst.rkey, remote_offsets=dst_offs, mr=src,
                offsets=rng.choice(8, size=k, replace=False)))
        elif kind == "read":
            k = int(rng.integers(1, 4))
            wrs.append(verbs.SendWR(
                wr_id=i, opcode=verbs.IBV_WR_RDMA_READ,
                remote_key=dst.rkey,
                remote_offsets=rng.choice(8, size=k, replace=False)))
    pair.client.post_send(wrs)
    processed = pair.client.flush()
    return dict(
        processed=processed, stalled=len(pair.client.sq),
        send_wcs=pair.client_cq.poll(), recv_wcs=pair.server_recv_cq.poll(),
        region=np.asarray(pair.pd.engine.regions["dst"]),
        descs=[np.asarray(ps.desc) for ps in pair.client.sq])


def _assert_same(a, b):
    assert a["processed"] == b["processed"]
    assert a["stalled"] == b["stalled"]
    np.testing.assert_array_equal(a["region"], b["region"])
    for da, db in zip(a["descs"], b["descs"]):
        np.testing.assert_array_equal(da, db)      # stalled WQEs bit-equal
    for key in ("send_wcs", "recv_wcs"):
        wa, wb = a[key], b[key]
        assert [(w.wr_id, w.opcode, w.status, w.length) for w in wa] == \
               [(w.wr_id, w.opcode, w.status, w.length) for w in wb], key
        for x, y in zip(wa, wb):
            if x.data is None or y.data is None:
                assert x.data is None and y.data is None
            else:
                np.testing.assert_array_equal(np.asarray(x.data),
                                              np.asarray(y.data))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.sampled_from(_KINDS), min_size=1, max_size=24),
       st.integers(0, 24), st.sampled_from([False, True]))
def test_dispatch_vectorized_bit_exact(kinds, n_recv, use_srq):
    """Random opcode mixes + random recv budgets (mid-chain RNR stalls
    when the budget runs short): completions, MR contents, stall points
    and stalled descriptors match the scalar transport exactly."""
    _assert_same(_run_chain(kinds, n_recv, use_srq, vectorized=True),
                 _run_chain(kinds, n_recv, use_srq, vectorized=False))


@pytest.mark.parametrize("kinds,device_runs,host_runs", [
    # ONE chain: `write_bad` (an unknown rkey) splits the WRITEs into
    # sub-runs, so the first and last land device to device into one
    # region in one flush; between them duplicate destination offsets,
    # a broadcasting WR and a payload-sourced WR each force the host path
    (["write_mr", "write_bad", "write_mr_dup", "write_mr_dup",
      "write_bad", "write_mr_bcast", "write_mr", "write_bad", "write_mr",
      "write", "write_mr", "write_bad", "write_mr"], 2, 3),
    (["write_mr_dup", "write_mr_dup"], 0, 1),
    (["write_mr", "write_mr_bcast", "write_mr"], 0, 1),
    (["write_mr", "write", "write_mr"], 0, 1),
    (["write_mr", "send_inline", "write_mr", "read", "write_mr"], 3, 0),
], ids=["one_chain", "dup_dst", "broadcast", "payload_between",
        "device_ops_around_send_and_read"])
def test_write_fallbacks_bit_exact(kinds, device_runs, host_runs):
    """MR-sourced WRITE sub-runs land device to device only where their
    shape allows; every fallback and every mix of device and host
    buffers in one flush stays bit-exact with the scalar oracle."""
    fused = metrics.get_registry().scope("fused")
    dev0 = fused.counter("device_write_runs").value
    host0 = fused.counter("host_write_runs").value
    vec = _run_chain(kinds, 4, False, vectorized=True)
    assert fused.counter("device_write_runs").value - dev0 == device_runs
    assert fused.counter("host_write_runs").value - host0 == host_runs
    _assert_same(vec, _run_chain(kinds, 4, False, vectorized=False))


def test_rnr_mid_chain_stalls_identically():
    kinds = ["send_big"] * 5 + ["write"] + ["send_big"] * 3
    for use_srq in (False, True):
        a = _run_chain(kinds, 4, use_srq, vectorized=True)
        b = _run_chain(kinds, 4, use_srq, vectorized=False)
        # 4 recvs: the 5th SEND stalls; the WRITE behind it must NOT jump
        # the queue (RC ordering)
        assert a["processed"] == b["processed"] == 4
        assert a["stalled"] == b["stalled"] == 5
        _assert_same(a, b)


# -- counters: the launch/DMA contracts the benchmarks report ----------------
def test_write_run_fuses_to_one_launch():
    pair = verbs.VerbsPair()
    dst = pair.pd.reg_mr("dst", np.zeros((64, 4), np.float32))
    before = pair.server.ctx.dma_launches
    pair.client.post_send([verbs.SendWR(
        wr_id=i, opcode=verbs.IBV_WR_RDMA_WRITE, remote_key=dst.rkey,
        remote_offsets=[i], payload=np.full((1, 4), float(i), np.float32))
        for i in range(32)])
    pair.client.flush()
    assert pair.server.ctx.dma_launches - before == 1   # ONE fused scatter
    assert len(pair.client_cq.poll()) == 32
    got = np.asarray(pair.pd.engine.regions["dst"])
    np.testing.assert_allclose(got[:32, 0], np.arange(32, dtype=np.float32))


def test_write_coalescing_last_write_wins_on_duplicate_offsets():
    pair = verbs.VerbsPair()
    dst = pair.pd.reg_mr("dst", np.zeros((4, 2), np.float32))
    pair.client.post_send([
        verbs.SendWR(wr_id=0, opcode=verbs.IBV_WR_RDMA_WRITE,
                     remote_key=dst.rkey, remote_offsets=[1],
                     payload=np.full((1, 2), 1.0, np.float32)),
        verbs.SendWR(wr_id=1, opcode=verbs.IBV_WR_RDMA_WRITE,
                     remote_key=dst.rkey, remote_offsets=[1, 2],
                     payload=np.stack([np.full(2, 2.0, np.float32),
                                       np.full(2, 3.0, np.float32)]))])
    pair.client.flush()
    got = np.asarray(pair.pd.engine.regions["dst"])
    np.testing.assert_allclose(got[1], 2.0)             # later WR won
    np.testing.assert_allclose(got[2], 3.0)


def test_only_read_write_boundaries_fence():
    """W W R R W: two fused write runs + one fused read run = 3 launches,
    and the reads observe exactly the writes submitted before them."""
    pair = verbs.VerbsPair()
    dst = pair.pd.reg_mr("dst", np.zeros((8, 2), np.float32))
    before = pair.server.ctx.dma_launches
    mk_w = lambda i, off, val: verbs.SendWR(
        wr_id=i, opcode=verbs.IBV_WR_RDMA_WRITE, remote_key=dst.rkey,
        remote_offsets=[off], payload=np.full((1, 2), val, np.float32))
    mk_r = lambda i, off: verbs.SendWR(
        wr_id=i, opcode=verbs.IBV_WR_RDMA_READ, remote_key=dst.rkey,
        remote_offsets=[off])
    pair.client.post_send([mk_w(0, 0, 5.0), mk_w(1, 1, 6.0),
                           mk_r(2, 0), mk_r(3, 1), mk_w(4, 0, 7.0)])
    pair.client.flush()
    assert pair.server.ctx.dma_launches - before == 3
    wcs = {w.wr_id: w for w in pair.client_cq.poll()}
    np.testing.assert_allclose(np.asarray(wcs[2].data), [[5.0, 5.0]])
    np.testing.assert_allclose(np.asarray(wcs[3].data), [[6.0, 6.0]])
    np.testing.assert_allclose(
        np.asarray(pair.pd.engine.regions["dst"])[0], 7.0)


def test_send_chain_publishes_one_ring_dma_per_cq():
    srq = verbs.SharedReceiveQueue(max_wr=256)
    pair = verbs.VerbsPair(srq=srq, depth=512, publish_every=64)
    srq.post_recv([verbs.RecvWR(wr_id=i) for i in range(100)])
    w0 = pair.server_recv_cq.ring.dma_writes
    pair.client.post_send([verbs.SendWR(wr_id=i, signaled=False,
                                        payload=np.array([i], np.int64))
                           for i in range(100)])
    pair.client.flush()
    assert pair.server_recv_cq.ring.dma_writes - w0 == 1
    assert [w.wr_id for w in pair.server_recv_cq.poll()] == list(range(100))


# -- SRQ take_many -----------------------------------------------------------
def test_take_many_matches_sequential_takes():
    def build(limit=3):
        events = []
        srq = verbs.SharedReceiveQueue(
            max_wr=64, srq_limit=limit,
            on_limit=lambda s: (events.append(len(s)), s.post_recv(
                [verbs.RecvWR(wr_id=50 + i) for i in range(4)])))
        srq.post_recv([verbs.RecvWR(wr_id=i) for i in range(6)])
        return srq, events

    a, ev_a = build()
    b, ev_b = build()
    got_a = a.take_many(qp_num=1, n=9)
    got_b = []
    while len(got_b) < 9:
        wr = b.take(qp_num=1)
        if wr is None:
            break
        got_b.append(wr)
    # the armed watermark fires MID-batch and its refill callback tops
    # the pool up; batched and sequential claims must see the same WRs
    assert [w.wr_id for w in got_a] == [w.wr_id for w in got_b]
    assert ev_a == ev_b and a.limit_events == b.limit_events == 1
    assert len(a) == len(b)
    assert a.taken_by_qp[1] == b.taken_by_qp[1] == 9


def test_take_many_short_claim_is_rnr():
    srq = verbs.SharedReceiveQueue(max_wr=8)
    srq.post_recv([verbs.RecvWR(wr_id=i) for i in range(3)])
    got = srq.take_many(qp_num=2, n=7)
    assert [w.wr_id for w in got] == [0, 1, 2]
    assert srq.take_many(qp_num=2, n=4) == []
    assert srq.taken_by_qp[2] == 3


# -- error paths: the batched fast paths must not over-claim -----------------
def test_send_run_failure_mid_run_releases_claims():
    """A payload that fails mid-run (bad reshape into the posted MR)
    must not redeliver the WRs that already completed, and must hand
    the pre-claimed recv WRs of the rest back to the pool front."""
    srq = verbs.SharedReceiveQueue(max_wr=16)
    pair = verbs.VerbsPair(srq=srq)
    mr = pair.pd.reg_mr("land", np.zeros((8, 4), np.float32))
    srq.post_recv([verbs.RecvWR(wr_id=0),
                   verbs.RecvWR(wr_id=1, mr=mr, offsets=[0]),
                   verbs.RecvWR(wr_id=2)])
    pair.client.post_send([
        verbs.SendWR(wr_id=0, payload=np.array([7], np.int64)),
        verbs.SendWR(wr_id=1, inline=False,            # 3 floats into a
                     payload=np.zeros(3, np.float32)),  # 4-wide record
        verbs.SendWR(wr_id=2, payload=np.array([9], np.int64))])
    with pytest.raises(TypeError):
        pair.client.flush()
    # WR 0 delivered (exactly once); WRs 1,2 still queued; their recv
    # WRs are back in pool-FIFO order
    wcs = pair.server_recv_cq.poll()
    assert [w.wr_id for w in wcs] == [0]
    assert [ps.wr.wr_id for ps in pair.client.sq] == [1, 2]
    assert [w.wr_id for w in srq._wrs] == [1, 2]
    assert srq.taken_by_qp[pair.server.qp_num] == 1


def test_write_run_failure_publishes_no_phantom_success():
    """A bad payload mid-WRITE-run must not publish SUCCESS CQEs for
    writes whose fused DMA was never submitted: the sub-run gathers
    every source before anything is staged (all-or-nothing), so the
    failing chain stays queued and the MR stays untouched."""
    pair = verbs.VerbsPair()
    dst = pair.pd.reg_mr("dst", np.zeros((4, 4), np.float32))
    pair.client.post_send([
        verbs.SendWR(wr_id=0, opcode=verbs.IBV_WR_RDMA_WRITE,
                     remote_key=dst.rkey, remote_offsets=[0],
                     payload=np.full((1, 4), 5.0, np.float32)),
        verbs.SendWR(wr_id=1, opcode=verbs.IBV_WR_RDMA_WRITE,
                     remote_key=dst.rkey, remote_offsets=[1],
                     payload=np.zeros(3, np.float32))])   # not 4-wide
    with pytest.raises((TypeError, ValueError)):
        pair.client.flush()
    assert pair.client_cq.poll() == []                    # no phantom CQE
    assert [ps.wr.wr_id for ps in pair.client.sq] == [0, 1]
    np.testing.assert_allclose(np.asarray(pair.pd.engine.regions["dst"]), 0)


def test_submit_dma_snapshots_mutable_buffers():
    """A host scratch buffer reused between submissions must be copied
    at submit time (Table-2 handlers loop over scratch); device arrays
    are immutable and stage as-is."""
    from repro.core.offload_engine import OffloadEngine, QPContext
    eng = OffloadEngine()
    eng.register_dma_region("mem", np.zeros((4, 2), np.float32))
    ctx = QPContext(0, eng)
    scratch = np.full((1, 2), 1.0, np.float32)
    ctx.submit_dma("WRITE", "mem", np.array([0]), 2, buf=scratch)
    scratch[:] = 9.0
    ctx.submit_dma("WRITE", "mem", np.array([1]), 2, buf=scratch)
    ctx._flush()
    got = np.asarray(eng.regions["mem"])
    np.testing.assert_allclose(got[0], 1.0)     # submit-time value
    np.testing.assert_allclose(got[1], 9.0)


def test_flush_error_does_not_orphan_pending_dmas():
    """A mid-flush failure (mixed record sizes assert) must leave the
    pending ops rescannable: a later wait re-reports the real error
    instead of a bare KeyError from a silently-skipped scan window."""
    from repro.core.offload_engine import OffloadEngine, QPContext
    eng = OffloadEngine()
    eng.register_dma_region("a", np.zeros((4, 2), np.float32))
    ctx = QPContext(0, eng)
    ctx.submit_dma("READ", "a", np.array([0]), 2)
    bad = ctx.submit_dma("READ", "a", np.array([1]), 1)    # mixed length
    with pytest.raises(AssertionError):
        ctx.wait_dma_finish(bad)
    with pytest.raises(AssertionError):    # still diagnosed, not orphaned
        ctx.wait_dma_finish(bad)
    ctx.reset()                            # teardown recovers the context
    ok = ctx.submit_dma("READ", "a", np.array([2]), 2)
    np.testing.assert_allclose(np.asarray(ctx.wait_dma_finish(ok)), 0.0)


# -- clients ride the vectorized path end to end -----------------------------
@pytest.mark.parametrize("vectorized", [True, False])
def test_verbs_pair_send_many_both_paths(vectorized):
    pair = verbs.VerbsPair(vectorized=vectorized, depth=256,
                           publish_every=16)
    wcs = pair.send_many([np.array([i], np.int64) for i in range(20)])
    assert [w.wr_id for w in wcs] == list(range(20))
    assert all(int(np.asarray(w.data)[0]) == i for i, w in enumerate(wcs))
