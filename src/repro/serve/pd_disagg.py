"""Prefill/decode disaggregation (paper §5.7 KVCache-transfer workload).

A prefill engine produces KV caches; a verbs SEND on a mesh-transport QP
ships them over the `pod` mesh axis (striped / "sprayed"); the decode
engine ingests them
into its paged pool and serves decode steps. On the CPU test rig the pod
axis degenerates to identity transfer, but every API, layout and
descriptor path is the production one — the multi-pod dry-run lowers the
same `make_transfer_step` on the (2,16,16) mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import verbs
from repro.core.descriptors import (make_descriptor, OP_KV_ACTIVATE,
                                    TransferPlan)
from repro.core.kvtransfer import KVTransferEngine
from repro.obs import metrics, trace
from repro.serve.kvcache import PagedKVPool, pad_caches
from repro.serve.paged import PagePool, bucket_len, bucketable, pageable


class PDServer:
    def __init__(self, model, params, *, max_seq: int = 128,
                 page_tokens: int = 16, quantize_bits: int = 0,
                 vectorized: bool = True, fabric=None):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.max_seq = max_seq
        self.page_tokens = page_tokens
        self.plan = TransferPlan(quantize_bits=quantize_bits)
        # batch-wise verbs dispatch on the transfer leg (scalar oracle
        # when False); threaded into the KVTransferEngine per transfer
        self.vectorized = vectorized
        # optional shared verbs fabric: when given, every transfer's
        # KVTransferEngine rides it (and its fabric-scope recv pool)
        # instead of spanning a private 2-pod grid per transfer
        self.fabric = fabric
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)

    # -- prefill pod ----------------------------------------------------
    def prefill(self, prompts: np.ndarray):
        """prompts: (B, P). Returns (first_tokens, caches, prefill_len)."""
        logits, caches = self._prefill(self.params, jnp.asarray(prompts))
        first = jnp.argmax(logits[:, -1], axis=-1)
        return first, caches, prompts.shape[1]

    # -- the wire ---------------------------------------------------------
    def transfer(self, caches, batch: int, seq_len: int, staged=False):
        """One verbs SEND per transfer: prefill is the client QP, decode
        the server; headers ride the CQ ring, payload the mesh wire.
        Delegates to KVTransferEngine — decode-side SRQ pool + CQ-credit
        flow control come with it, and the transfer path lives in ONE
        place."""
        eng = KVTransferEngine(self.model, batch, seq_len, self.plan,
                               vectorized=self.vectorized,
                               fabric=self.fabric)
        try:
            data = eng.transfer_staged(caches) if staged else \
                eng.transfer(caches)
        finally:
            if self.fabric is not None:
                # per-transfer engine on a LONG-LIVED shared fabric:
                # release its listener/QPs/routes or the fabric grows
                # per call
                eng.close()
        return data, eng.stats

    # -- decode pod (with paged ingest) ----------------------------------
    def ingest_and_decode(self, caches, first_tokens, prefill_len: int,
                          n_steps: int = 8, use_kernel: bool = False,
                          interpret: bool = False):
        """Ingest transferred caches through the paged pool (T2), gather
        back to the decode layout, then run greedy decode steps."""
        caches = pad_caches(caches, prefill_len, self.max_seq)
        caches = self._page_roundtrip(caches, use_kernel=use_kernel,
                                      interpret=interpret)
        B = first_tokens.shape[0]
        toks = jnp.asarray(first_tokens)[:, None].astype(jnp.int32)
        out = [np.asarray(toks[:, 0])]
        pos = jnp.full((B,), prefill_len, jnp.int32)
        for _ in range(n_steps):
            logits, caches = self._decode(self.params, toks, caches, pos)
            toks = jnp.argmax(logits[:, :1], axis=-1).astype(jnp.int32)
            if toks.ndim == 1:
                toks = toks[:, None]
            out.append(np.asarray(toks[:, 0]))
            pos = pos + 1
        return np.stack(out, 1)

    def _page_roundtrip(self, caches, use_kernel: bool, interpret: bool):
        """Every seq-indexed cache leaf takes the paged ingest+gather path."""
        def one(a):
            if a.ndim < 3 or a.shape[2] != self.max_seq:
                return a                    # state/window caches pass through
            lead = a.shape[:2]              # (L, B)
            flat = a.reshape((-1, self.max_seq) + a.shape[3:])
            outs = []
            for row in range(flat.shape[0]):
                kv = flat[row]
                pool = PagedKVPool(
                    n_pages=-(-self.max_seq // self.page_tokens),
                    page_tokens=self.page_tokens,
                    feature_shape=kv.shape[1:], dtype=kv.dtype)
                alloc = pool.allocate(self.max_seq)
                pool.ingest(alloc, kv, use_kernel=use_kernel,
                            interpret=interpret)
                outs.append(pool.gather(alloc, self.max_seq))
            return jnp.stack(outs).reshape(lead + (self.max_seq,) + a.shape[3:])
        return jax.tree.map(one, caches)

    # -- end to end -------------------------------------------------------
    def serve(self, prompts: np.ndarray, n_steps: int = 8, staged=False,
              use_kernel: bool = False, interpret: bool = False):
        first, caches, plen = self.prefill(prompts)
        caches, stats = self.transfer(caches, prompts.shape[0], plen,
                                      staged=staged)
        toks = self.ingest_and_decode(caches, first, plen, n_steps,
                                      use_kernel=use_kernel,
                                      interpret=interpret)
        return toks, stats


class PrefillPod:
    """One prefill pod of a disaggregated serving cluster (ISSUE 10).

    The pod owns a single-slot staging `PagePool` on its OWN protection
    domain: a prompt is prefilled here (bucketed to a power-of-two pad
    when the model allows), its caches land in staged pages, and the
    pages move to a decode pod as one-sided RDMA_WRITEs through
    `KVTransferEngine.migrate_pages` — one WR per page, fusing to ONE
    gather launch per cache leaf. The request then goes live with an
    inline OP_KV_ACTIVATE descriptor SENT to the decode engine's own
    notification ring (the same ring `submit()` uses), which is also the
    admission-counted traffic a seeded `FaultModel.kill_after` can take
    the decode pod down with mid-run: migration AND activation replay
    through the surviving pod, re-reserving pages there first.

    `reserve()` is called directly on the decode `ServeEngine` object —
    the control-plane RPC of the real system, kept as a method call on
    this in-process rig; the *data* plane (pages, activation) is all
    verbs traffic.
    """

    requests_processed = metrics.counter_attr()

    def __init__(self, model, params, *, fabric, gid: str,
                 decode_gids: list[str], max_seq: int = 256,
                 page_tokens: int = 16):
        metrics.instance_scope(self, "prefillpod", indexed=True)
        assert pageable(model), "PrefillPod needs a pageable cache"
        self.requests_processed = 0
        self.model = model
        self.fabric = fabric
        self.gid = gid
        # on a grid with one chip per gid, the pod's params and staging
        # pages live on its own chip; migrations then cross chips
        self.device = fabric.device_of(gid)
        self.params = params if self.device is None else \
            jax.device_put(params, self.device)
        self.max_seq = max_seq
        self.bucketed = bucketable(model)
        self.pool = PagePool(model, fabric.node(gid).pd, max_batch=1,
                             max_seq=max_seq, page_tokens=page_tokens,
                             device=self.device)
        self.kv = KVTransferEngine(model, 1, max_seq, fabric=fabric,
                                   src_gid=gid, decode_gids=decode_gids)
        # compiles as `jit_prefill`: the benchmark's device-trace
        # readers find prefill time under that program name
        self._prefill = jax.jit(model.prefill)
        # per-decode-gid activation endpoints (to the ENGINE listeners,
        # not the kv transfer listeners): gid -> (ep, lost-flag box)
        self._act_eps: dict[str, tuple] = {}

    def close(self):
        for ep, _ in self._act_eps.values():
            if ep.qp.qp_num in self.fabric.qps:
                self.fabric.disconnect(ep)
        self._act_eps.clear()
        self.kv.close()
        self.pool.close()
        return self

    def _run_prefill(self, prompt: np.ndarray):
        plen = int(prompt.size)
        if self.bucketed:
            pad = bucket_len(plen, self.max_seq)
            padded = np.zeros((1, pad), np.int32)
            padded[0, :plen] = prompt
            return self._prefill(self.params, jnp.asarray(padded),
                                 last_pos=jnp.asarray([plen - 1],
                                                      jnp.int32))
        return self._prefill(self.params, jnp.asarray(prompt[None, :]))

    def _engine_ep(self, engine):
        """The (cached) activation connection to a decode engine's
        listener — made through the fabric address, like any client."""
        ent = self._act_eps.get(engine.gid)
        if ent is not None and (ent[1][0] or
                                ent[0].qp.qp_num not in self.fabric.qps):
            if ent[0].qp.qp_num in self.fabric.qps:
                self.fabric.disconnect(ent[0])
            self._act_eps.pop(engine.gid)
            ent = None
        if ent is None:
            lost = [False]

            def on_lost(_ep, lost=lost):
                lost[0] = True
            ep = self.fabric.connect(engine._listen_addr, src_gid=self.gid,
                                     depth=64, on_disconnect=on_lost)
            ent = self._act_eps[engine.gid] = (ep, lost)
        return ent

    def _activate_once(self, engine, rid: int, plen: int) -> bool:
        """Send the go-live descriptor to the decode engine's ring. False
        means the decode pod died before (or during — the kill-mid-flush
        trigger) the SEND: the caller fails over and replays."""
        ep, lost = self._engine_ep(engine)
        if lost[0]:
            return False
        d = make_descriptor(OP_KV_ACTIVATE, src=rid, length=plen)
        try:
            ep.post_send(verbs.SendWR(wr_id=rid,
                                      payload=np.asarray(d, np.int64),
                                      inline=True, signaled=False))
            ep.flush()
        except verbs.QPStateError:
            return False
        if lost[0]:
            ep.poll()                       # drain WR_FLUSH_ERR
            return False
        return True

    def process(self, rid: int, prompt, max_new_tokens: int,
                engines: dict, *, decode_gid: str | None = None) -> str:
        """One disaggregated request end to end: prefill here, stage
        pages, migrate them into the pages the chosen decode engine
        `reserve()`d, activate. Returns the gid that owns the request
        (the survivor, if the chosen pod died mid-flight)."""
        prompt = np.asarray(prompt, np.int32).ravel()
        plen = int(prompt.size)
        tr = trace.TRACER
        if tr is not None and not tr.detail:
            tr = None
        t0 = tr.now() if tr is not None else 0
        logits, caches = self._run_prefill(prompt)
        first_tok = int(jnp.argmax(logits[0, -1]))
        if tr is not None:
            tr.complete("serve.prefill", t0, tid=self.gid, rid=rid,
                        tokens=plen, bucket=bucket_len(plen, self.max_seq)
                        if self.bucketed else plen)
            t0 = tr.now()
        src_ids = self.pool.alloc(self.pool.pages_for(plen))
        self.pool.fill(src_ids, caches)
        if tr is not None:
            tr.complete("serve.page_fill", t0, tid=self.gid, rid=rid,
                        pages=len(src_ids))
        if decode_gid is not None:
            self.kv.retarget(decode_gid)

        def reserve_on(gid):
            lease = engines[gid].reserve(rid, plen, max_new_tokens,
                                         first_tok)
            return [(mr, src_ids, rkey, dst_ids)
                    for mr, (rkey, dst_ids) in zip(self.pool.mrs, lease)]

        def migrate(runs):
            t0 = tr.now() if tr is not None else 0
            landed = self.kv.migrate_pages(runs, retarget=reserve_on)
            if tr is not None:
                tr.complete("serve.migrate", t0, tid=self.gid, rid=rid,
                            pages=len(src_ids))
            return landed

        def activate(gid):
            t0 = tr.now() if tr is not None else 0
            ok = self._activate_once(engines[gid], rid, plen)
            if tr is not None:
                tr.complete("serve.activate", t0, tid=self.gid, rid=rid,
                            decode=gid)
            return ok

        try:
            landed = migrate(reserve_on(self.kv.decode_gid))
            for _ in range(self.kv.replay_limit + 1):
                if activate(landed):
                    break
                # pod died between migrate and activation: same replay
                # as a mid-migrate death — survivor re-reserves, pages
                # re-migrate, activation re-sends
                self.kv._failover()
                landed = migrate(reserve_on(self.kv.decode_gid))
            else:
                raise verbs.QPStateError(
                    f"request {rid}: activation failed after "
                    f"{self.kv.replay_limit + 1} attempts")
        finally:
            self.pool.free(src_ids)
        self.requests_processed += 1
        return landed
