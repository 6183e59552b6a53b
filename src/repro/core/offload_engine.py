"""T4 — programmable offloading engine (paper §3.5, Table 2, Listing 1).

Cloud-provider code registers an unused opcode with a handler; when a
packet bearing that opcode arrives, the engine invokes the handler with
the Table-2 API surface:

    register_opcode(opcode, qp, func)
    register_dma_region(host_addr, size)      -> here: a named device array
    alloc_resp(context, size)
    submit_dma(context, op, host_addr, arm_addr, size) -> dma_id
    wait_dma_finish(context, dma_id)
    submit_resp(context, addr, size)

TPU adaptation: "DMA" ops against a registered region are *queued* and
executed as one fused gather/scatter at wait time — the coalescing that
makes the batched-READ opcode beat N independent reads (paper Fig. 16b) is
structural, not emulated. Handlers run as ordinary python coroutines
(the paper runs them as user-space coroutines on spare Arm cores).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.descriptors import (OP_BATCH_READ, OP_LIST_TRAVERSAL)
from repro.kernels.wr_scatter import ops as wr_scatter_ops


def dedupe_last_wins(offs: np.ndarray, vals):
    """Sequential-retirement semantics for a fused scatter: when target
    offsets repeat, keep only the LAST update per offset (XLA leaves the
    order of duplicate scatter indices unspecified). Shared by every
    layer that stacks WRITEs — `QPContext._flush` and the transport's
    run fusion must agree bit-for-bit."""
    if np.unique(offs).size == offs.size:
        return offs, vals
    _, first_rev = np.unique(offs[::-1], return_index=True)
    keep = np.sort(offs.size - 1 - first_rev)
    return offs[keep], vals[keep]


@dataclass
class DmaOp:
    op: str                     # READ | WRITE
    region: str
    offsets: np.ndarray         # element offsets into the region
    length: int                 # elements per offset
    buf: object = None          # WRITE source rows (numpy or device array)


@dataclass
class QPContext:
    qp_id: int
    engine: "OffloadEngine"
    resp: jnp.ndarray | None = None
    _dma_queue: list = field(default_factory=list)
    _dma_done: dict = field(default_factory=dict)
    dma_launches: int = 0       # fused launches (for Fig. 16 accounting)
    # fuse consecutive WRITEs to one region into a single scatter launch;
    # False = one launch per WRITE (the scalar perf/bit-exactness oracle)
    coalesce_writes: bool = True
    # every op below this index has retired (a _flush retires ALL pending
    # ops), so a long-lived QP's flush scans only the ops queued since —
    # not its whole DMA history
    _scan_from: int = 0

    # ---- Table 2 API ----
    def alloc_resp(self, size: int, dtype=jnp.float32):
        self.resp = jnp.zeros((size,), dtype)
        return self.resp

    def submit_dma(self, op: str, region: str, offsets, length: int,
                   buf=None) -> int:
        """Queue one DMA. WRITEs carry their source data in `buf`
        (record rows matching `offsets`); READs leave it None. A
        mutable host buffer is SNAPSHOTTED at submission (the caller
        may reuse it — Table-2 handlers loop over scratch); a device
        array is immutable, so it stages as-is and the one device
        conversion happens at the fused scatter, not per submission."""
        dma_id = len(self._dma_queue)
        if buf is not None and not isinstance(buf, jnp.ndarray):
            buf = np.array(buf)
        self._dma_queue.append(
            DmaOp(op, region, np.asarray(offsets, np.int32), length, buf))
        return dma_id

    def wait_dma_finish(self, dma_id: int):
        if dma_id not in self._dma_done:
            self._flush()
        return self._dma_done[dma_id]

    def _flush(self):
        """Coalesce queued DMAs against the same region into fused
        launches (the batched-DMA win). Offsets are record indices;
        `length` is the record size in elements. Ops against one region
        retire in submission order — only a READ->WRITE or WRITE->READ
        boundary fences, so read-after-write sees the write (RC
        ordering) while a write-free batch of N reads costs ONE gather
        and a read-free batch of N writes ONE scatter.

        The coalescing path launches through the fused jitted ops
        (`kernels/wr_scatter/ops`, counted as `fused/launches`; scatter
        DONATES the outgoing region buffer). The oracle
        (`coalesce_writes=False`) keeps eager per-op `at[].set`/`take`
        calls — it never compiles, by contract."""
        pending = [(i, d) for i, d in enumerate(
            self._dma_queue[self._scan_from:], start=self._scan_from)
            if i not in self._dma_done]
        by_region: dict[str, list[tuple[int, DmaOp]]] = {}
        for i, d in pending:
            by_region.setdefault(d.region, []).append((i, d))
        for region, items in by_region.items():
            reads: list[tuple[int, DmaOp]] = []
            writes: list[tuple[int, DmaOp]] = []

            def gather_run():
                if not reads:
                    return
                arr = self.engine.regions[region]
                L = reads[0][1].length
                assert all(d.length == L for _, d in reads), \
                    "mixed record sizes in one flush group"
                offs = np.concatenate([d.offsets.ravel() for _, d in reads])
                if self.coalesce_writes:
                    flat = wr_scatter_ops.gather_records(arr, offs, L)
                else:
                    flat = jnp.take(jnp.reshape(arr, (-1, L)),
                                    jnp.asarray(offs, jnp.int32), axis=0)
                self.dma_launches += 1
                c = 0
                for i, d in reads:
                    n = d.offsets.size
                    self._dma_done[i] = flat[c:c + n]
                    c += n
                reads.clear()

            def scatter_one(i: int, d: DmaOp):
                arr = self.engine.regions[region]
                if self.coalesce_writes:
                    self.engine.regions[region] = \
                        wr_scatter_ops.scatter_one(arr, d.offsets, d.buf)
                else:
                    self.engine.regions[region] = arr.at[d.offsets].set(d.buf)
                self._dma_done[i] = True
                self.dma_launches += 1

            def scatter_run():
                if not writes:
                    return
                if len(writes) == 1:
                    scatter_one(*writes[0])
                    writes.clear()
                    return
                arr = self.engine.regions[region]
                rec_shape = tuple(arr.shape[1:])
                bufs = []
                for _, d in writes:
                    try:
                        # numpy-first: one host-side stack, ONE device
                        # conversion at the scatter (a variadic device
                        # concat over many tiny bufs costs more than the
                        # scatter itself)
                        bufs.append(np.asarray(d.buf).reshape(
                            (d.offsets.size,) + rec_shape))
                    except (TypeError, ValueError):
                        # a broadcasting WRITE (buf rows != offsets) keeps
                        # its own scatter; retire the fused run first so
                        # submission order is preserved
                        bufs = None
                        break
                if bufs is None:
                    for i, d in writes:
                        scatter_one(i, d)
                    writes.clear()
                    return
                offs = np.concatenate(
                    [d.offsets.ravel() for _, d in writes]).astype(np.int64)
                vals = np.concatenate(bufs) if len(bufs) > 1 else bufs[0]
                offs, vals = dedupe_last_wins(offs, vals)
                # scatter_run only exists on the coalescing path (the
                # oracle scatters per-op above): always a fused launch
                self.engine.regions[region] = wr_scatter_ops.scatter_records(
                    self.engine.regions[region], offs, vals)
                self.dma_launches += 1
                for i, _ in writes:
                    self._dma_done[i] = True
                writes.clear()

            for i, d in items:
                if d.op == "READ":
                    scatter_run()       # WRITE -> READ boundary fences
                    reads.append((i, d))
                elif self.coalesce_writes:
                    gather_run()        # READ -> WRITE boundary fences
                    writes.append((i, d))
                else:                   # oracle: one launch per WRITE
                    gather_run()
                    scatter_one(i, d)
            gather_run()
            scatter_run()
        # advance only once everything retired: a mid-flush error leaves
        # the survivors rescannable by the next flush instead of orphaned
        self._scan_from = len(self._dma_queue)

    def submit_resp(self, buf):
        self.resp = buf
        return buf

    def reset(self):
        """Drop queued/retired DMA state (QP teardown): anything not yet
        waited on is abandoned, matching a hardware queue-pair reset."""
        self._dma_queue.clear()
        self._dma_done.clear()
        self._scan_from = 0
        self.resp = None
        return self


class OffloadEngine:
    def __init__(self):
        self.handlers: dict[int, Callable] = {}
        self.regions: dict[str, jnp.ndarray] = {}
        self._qps: dict[int, QPContext] = {}

    # ---- Table 2 API ----
    def register_opcode(self, opcode: int, qp_id: int, func: Callable):
        self.handlers[opcode] = func
        self._qps.setdefault(qp_id, QPContext(qp_id, self))

    def register_dma_region(self, name: str, array) -> str:
        self.regions[name] = jnp.asarray(array)
        return name

    def bind_context(self, qp_id: int, ctx: QPContext):
        """Adopt an externally-owned QPContext (the verbs layer creates
        one per QueuePair) so `handle_packet` dispatches into it."""
        self._qps[qp_id] = ctx
        return ctx

    def unbind_context(self, qp_id: int):
        """Release a QP's context (ibv_destroy_qp): queued DMAs are
        abandoned, handler dispatch for this qp_id gets a fresh context."""
        ctx = self._qps.pop(qp_id, None)
        if ctx is not None:
            ctx.reset()
        return ctx

    def handle_packet(self, opcode: int, packet, qp_id: int = 0):
        """Network-stack dispatch: a packet with a registered opcode is
        treated as a SEND, delivered, then handed to the engine."""
        if opcode not in self.handlers:
            raise KeyError(f"opcode {opcode:#x} not registered")
        ctx = self._qps.setdefault(qp_id, QPContext(qp_id, self))
        self.handlers[opcode](packet, ctx)
        return ctx.resp


# --------------------------------------------------------------------------
# Shipped opcodes (paper §5.6 / Listing 1)
# --------------------------------------------------------------------------
def install_batched_read(engine: OffloadEngine, region: str, value_size: int,
                         qp_id: int = 0) -> int:
    """Paper Listing 1: aggregate N scattered reads into one request; the
    server fetches all values with coalesced DMA and answers once."""
    def handle_batch_read(packet, ctx: QPContext):
        offsets = np.asarray(packet, np.int32)           # target offsets
        ctx.alloc_resp(offsets.size * value_size)
        # ONE submit_dma carrying every offset (Listing 1's aggregation):
        # submitting N single-offset DMAs would defeat the coalescing the
        # opcode exists to demonstrate
        dma_id = ctx.submit_dma("READ", region, offsets, value_size)
        ctx.submit_resp(ctx.wait_dma_finish(dma_id).ravel())

    engine.register_opcode(OP_BATCH_READ, qp_id, handle_batch_read)
    return OP_BATCH_READ


def install_list_traversal(engine: OffloadEngine, region: str, qp_id: int = 0,
                           value_size: int = 8, max_hops: int = 64) -> int:
    """Paper §5.6: server-side linked-list walk. The region holds records
    [key, next_ptr, value...]; the handler chases pointers with on-device
    while_loop instead of N network round-trips."""
    rec = 2 + value_size

    def handle_traverse(packet, ctx: QPContext):
        target_key = jnp.asarray(packet[0])
        head = jnp.asarray(packet[1], jnp.int32)
        arr = engine.regions[region].reshape(-1, rec)

        def cond(state):
            ptr, hops = state
            return (arr[ptr, 0] != target_key) & (ptr >= 0) & (hops < max_hops)

        def body(state):
            ptr, hops = state
            return arr[ptr, 1].astype(jnp.int32), hops + 1

        ptr, hops = jax.lax.while_loop(cond, body, (head, jnp.int32(0)))
        ctx.dma_launches += 1        # one fused on-device walk
        ctx.submit_resp(arr[ptr, 2:])

    engine.register_opcode(OP_LIST_TRAVERSAL, qp_id, handle_traverse)
    return OP_LIST_TRAVERSAL
