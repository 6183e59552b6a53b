"""One cell's serving cluster, driven through its front end.

`Session` builds the cluster of a configuration's deployment (Router ->
PrefillPods -> RDMA_WRITE page migration -> paged decode ServeEngines,
all on one `verbs.Fabric`) around the benchmark's weights, warms every
shape the traffic will use, and drives one window of traffic through
`Router.submit` / `Router.step`.

Timing is the harness's own, taken around the calls it makes:

  * TTFT: from a request's scheduled send time to the end of the
    `Router.step` after which a decode pod holds its first token;
  * per-token times: token counts read after every `Router.step`;
  * spans: the harness wraps the pods' and engines' entry points on
    their instances (`PrefillPod.process`, `_run_prefill`,
    `_activate_once`, `KVTransferEngine.migrate_pages`,
    `ServeEngine.step`) with a timer, and with a
    `jax.profiler.TraceAnnotation` of the same name in a traced run, so
    the device trace can say what the host was doing in each idle gap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import jax
import numpy as np

from bench import traffic as traffic_mod

# lowering to MLIR, XLA compilation (or a compile-cache load), cache hits
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DRAIN_S = 60.0          # how long requests due in the window may finish late

GID_FMT = "pod{}/dev0"


class CompileClock:
    """Counts lowerings and backend compiles (JAX monitoring events) and
    the seconds they took, process-wide."""

    def __init__(self):
        self.lowerings = 0
        self.compiles = 0
        self.compile_s = 0.0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, fun_name=None, **_):
        if event == LOWER_EVENT:
            self.lowerings += 1
            self.compile_s += secs
            self.names.append(str(fun_name))
        elif event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self) -> tuple[int, int, float]:
        return self.lowerings, self.compiles, self.compile_s


class Spans:
    """Host spans recorded around the program's entry points."""

    def __init__(self):
        self.rows: dict[str, list] = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str, key=None):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield
        self.rows.setdefault(name, []).append((t0, time.perf_counter(),
                                               key))

    def wrap(self, obj, attr: str, name: str, key_arg: int | None = None,
             keep_result: bool = False):
        fn = getattr(obj, attr)

        def wrapped(*args, **kw):
            key = args[key_arg] if key_arg is not None else None
            with self.span(name, key) as _:
                out = fn(*args, **kw)
            if keep_result:
                t0, t1, k = self.rows[name][-1]
                self.rows[name][-1] = (t0, t1, (k, out))
            return out
        setattr(obj, attr, wrapped)

    def between(self, name: str, t0: float, t1: float) -> list:
        return [r for r in self.rows.get(name, ()) if t0 <= r[0] < t1]


@dataclasses.dataclass
class Rec:
    """One request as the client saw it."""
    rid: int
    req: traffic_mod.Request
    sched: float                   # scheduled send time (perf_counter)
    submitted: float
    tokens: int = 0
    t_first: float | None = None
    t_done: float | None = None
    served: list | None = None


@dataclasses.dataclass
class Window:
    start: float
    end: float
    seconds: float
    recs: list[Rec]
    window_tokens: int             # output tokens produced in the window
    decode_ctx: list[int]          # attended tokens of each decode token
    lateness: list[float]
    compiles: tuple[int, int, float]
    compiled_names: list[str]
    verbs_spans: list | None = None
    trace_summary: object = None


def build_cluster(model, params, dep: dict):
    from repro import verbs
    from repro.serve.engine import ServeEngine
    from repro.serve.pd_disagg import PrefillPod
    from repro.serve.router import Router
    n_pre, n_dec = int(dep["prefill_pods"]), int(dep["decode_pods"])
    pre = [GID_FMT.format(i) for i in range(n_pre)]
    dec = [GID_FMT.format(n_pre + i) for i in range(n_dec)]
    fabric = verbs.Fabric(pods=n_pre + n_dec)
    router = Router(fabric)
    for g in dec:
        router.add_decode(ServeEngine(
            model, params, max_batch=int(dep["slots_per_decode_pod"]),
            max_seq=int(dep["max_seq"]), fabric=fabric, gid=g,
            service=f"serve/{g}", page_tokens=int(dep["page_tokens"])))
    for g in pre:
        router.add_prefill(PrefillPod(
            model, params, fabric=fabric, gid=g, decode_gids=dec,
            max_seq=int(dep["max_seq"]),
            page_tokens=int(dep["page_tokens"])))
    return router


class Session:
    def __init__(self, model, params, config: dict, clock: CompileClock):
        self.model = model
        self.config = config
        self.dep = config["deployment"]
        self.clock = clock
        self.spans = Spans()
        self.router = build_cluster(model, params, self.dep)
        for pod in self.router.prefill_pods:
            self.spans.wrap(pod, "process", "prefill_pod", key_arg=0)
            self.spans.wrap(pod, "_run_prefill", "prefill")
            self.spans.wrap(pod, "_activate_once", "activate")
            self.spans.wrap(pod.kv, "migrate_pages", "migrate")
        for eng in self.router.engines.values():
            self.spans.wrap(eng, "step", "decode", keep_result=True)

    # -- bookkeeping -------------------------------------------------------
    def _count(self, rid: int) -> tuple[int, list | None]:
        r = self.router
        if rid in r._results:
            return len(r._results[rid]), r._results[rid]
        gid = r._owner.get(rid)
        if gid is None:
            return 0, None
        live = r.engines[gid].requests.get(rid)
        return (len(live.out_tokens) if live is not None else 0), None

    def _submit(self, req, sched: float, inflight: dict) -> Rec:
        rid = self.router.submit(req.prompt, max_new_tokens=req.max_new)
        rec = Rec(rid, req, sched, time.perf_counter())
        inflight[rid] = rec
        return rec

    def _step(self, inflight: dict, w_end: float, acc: dict) -> list[Rec]:
        """One Router.step, then read every in-flight request's token
        count. Returns the requests that finished in it."""
        with self.spans.span("router_step"):
            self.router.step()
        t = time.perf_counter()
        done = []
        with self.spans.span("track"):
            for rid, rec in list(inflight.items()):
                n, served = self._count(rid)
                if n > rec.tokens:
                    if rec.t_first is None:
                        rec.t_first = t
                    if t <= w_end:
                        acc["tokens"] += n - rec.tokens
                        plen = int(rec.req.prompt.size)
                        acc["ctx"].extend(plen + j for j in
                                          range(max(rec.tokens, 1), n))
                    rec.tokens = n
                if served is not None:
                    rec.t_done, rec.served = t, served
                    del inflight[rid]
                    done.append(rec)
        return done

    # -- warm-up -----------------------------------------------------------
    def warm(self, tr: traffic_mod.Traffic) -> int:
        """Serve one request per page count the traffic can produce (the
        eager page-fill shapes and the gather/scatter buckets), then two
        per prefill bucket (each prefill pod's own jit), so that every
        program the window runs has been compiled or loaded. Returns
        the number of warm-up requests."""
        from repro.serve.paged import bucket_len
        pt = int(self.dep["page_tokens"])
        max_seq = int(self.dep["max_seq"])
        lens = sorted({int(r.prompt.size) for r in tr.all_requests()})
        by_pages = {}
        for n in lens:
            by_pages.setdefault(-(-n // pt), n)
        by_bucket = {}
        for n in lens:
            by_bucket.setdefault(bucket_len(n, max_seq), n)
        plan = list(by_pages.values())
        plan += [n for n in by_bucket.values() for _ in range(2)]
        rng = np.random.default_rng(0)
        vocab = int(self.config["vocab_size"])
        # all at once: the router spreads them over every decode pod and
        # alternates prefill pods, so the pairs reach both pods' jits
        for n in plan:
            self.router.submit(rng.integers(0, vocab, n, dtype=np.int32),
                               max_new_tokens=3)
        self.router.run_until_done()
        return len(plan)

    # -- the window --------------------------------------------------------
    def run(self, tr: traffic_mod.Traffic, seconds: float,
            tracer=None) -> Window:
        """Drive the traffic for `seconds`, then let the requests due in
        the window finish (up to `DRAIN_S`). `tracer`: an object with
        start()/stop() around the window (the device trace)."""
        inflight: dict[int, Rec] = {}
        recs: list[Rec] = []
        lateness: list[float] = []
        acc = {"tokens": 0, "ctx": []}
        c0 = self.clock.snapshot()
        n0 = len(self.clock.names)
        if tracer is not None:
            tracer.start()
            self.spans.annotate = True
        start = time.perf_counter()
        end = start + seconds
        ann = jax.profiler.TraceAnnotation("bench.window") \
            if tracer is not None else contextlib.nullcontext()
        with ann:
            if tr.arrivals == "poisson":
                self._open_loop(tr, start, end, inflight, recs, lateness,
                                acc)
            else:
                self._closed_loop(tr, start, end, inflight, recs, acc)
        stop = time.perf_counter()
        c1 = self.clock.snapshot()
        summary = verbs = None
        if tracer is not None:
            self.spans.annotate = False
            summary, verbs = tracer.stop()
        drain_end = time.perf_counter() + DRAIN_S
        while inflight and time.perf_counter() < drain_end:
            self._step(inflight, end, acc)
        return Window(start, end, stop - start, recs, acc["tokens"],
                      acc["ctx"], lateness,
                      tuple(b - a for a, b in zip(c0, c1)),
                      self.clock.names[n0:n0 + c1[0] - c0[0]],
                      verbs, summary)

    def _open_loop(self, tr, start, end, inflight, recs, lateness, acc):
        reqs = tr.requests
        i = 0
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            while i < len(reqs) and start + reqs[i].at <= now:
                recs.append(self._submit(reqs[i], start + reqs[i].at,
                                         inflight))
                lateness.append(recs[-1].submitted - recs[-1].sched)
                i += 1
            if inflight:
                self._step(inflight, end, acc)
            else:
                nxt = start + reqs[i].at if i < len(reqs) else end
                with self.spans.span("wait"):
                    time.sleep(max(0.0, min(nxt, end) - now))
        # every request of the mix is due inside the window
        while i < len(reqs):
            recs.append(self._submit(reqs[i], start + reqs[i].at, inflight))
            lateness.append(recs[-1].submitted - recs[-1].sched)
            i += 1

    def _closed_loop(self, tr, start, end, inflight, recs, acc):
        nxt = {c: 0 for c in range(len(tr.rounds[0]))}

        def send(c, t):
            r = nxt[c]
            if r >= len(tr.rounds):
                raise RuntimeError("closed loop ran out of rounds: raise "
                                   "the mix's rounds")
            nxt[c] = r + 1
            recs.append(self._submit(tr.rounds[r][c], t, inflight))

        for c in nxt:
            send(c, start)
        while time.perf_counter() < end:
            for rec in self._step(inflight, end, acc):
                if rec.t_done < end:
                    send(rec.req.client, rec.t_done)

    def memory_peak(self) -> int | None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def close(self) -> None:
        """Free the cluster's device state (pools, staging pages); the
        weights stay with the caller."""
        self.router.close()
        self.router = None
        gc.collect()
