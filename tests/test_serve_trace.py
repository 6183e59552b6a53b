"""The serving cluster's spans (`repro.obs.trace` with ``detail`` on),
the clock anchors that lay the ring over the JAX profiler's timeline,
and the device program names the benchmark's device-trace readers look
up.

  * every request records submit -> admit -> prefill -> page fill ->
    migrate (with its source fetch and landing inside) -> activate ->
    activated, and tracing leaves the served tokens bit-exact;
  * a tracer without ``detail`` records only the datapath chain;
  * a ring span recorded inside a profiler annotation maps inside it;
  * the programs compile as ``jit_step``, ``jit_prefill``,
    ``jit__gather`` and ``jit__scatter``.
"""
import glob
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import verbs
from repro.configs.base import get_config, reduced
from repro.kernels.wr_scatter import ops as wr_scatter_ops
from repro.models.registry import build_model
from repro.obs import trace
from repro.serve.engine import ServeEngine
from repro.serve.pd_disagg import PrefillPod
from repro.serve.router import Router

DECODE_GIDS = ["pod2/dev0", "pod3/dev0"]
PREFILL_GIDS = ["pod0/dev0", "pod1/dev0"]
# two or more pages each (page_tokens 8), so every migration fuses its
# per-page WRs into one source fetch per cache leaf
PROMPTS = [[(7 * i + j) % 97 + 1 for j in range(n)]
           for i, n in enumerate((12, 20, 30))]
LIFECYCLE = ("serve.submit", "serve.admit", "serve.prefill",
             "serve.page_fill", "serve.migrate", "serve.activate",
             "serve.activated")


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced(get_config("gemma-2b"))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _no_tracer():
    yield
    trace.uninstall()


def _serve(model, params):
    fabric = verbs.Fabric(pods=4)
    router = Router(fabric)
    for g in DECODE_GIDS:
        router.add_decode(ServeEngine(
            model, params, max_batch=2, max_seq=64, fabric=fabric, gid=g,
            service=f"serve/{g}", page_tokens=8))
    for g in PREFILL_GIDS:
        router.add_prefill(PrefillPod(
            model, params, fabric=fabric, gid=g, decode_gids=DECODE_GIDS,
            max_seq=64, page_tokens=8))
    rids = [router.submit(p, max_new_tokens=4) for p in PROMPTS]
    res = router.run_until_done()
    router.close()
    return [res[r] for r in rids], rids


def test_every_request_records_its_lifecycle_in_order(gemma):
    model, params = gemma
    plain, _ = _serve(model, params)
    with trace.tracing(detail=True) as t:
        traced, rids = _serve(model, params)
    assert traced == plain
    assert t.dropped == 0
    ev = t.events()
    for rid in rids:
        mine = [e for e in ev if e[5].get("rid") == rid]
        names = [e[1] for e in mine]
        assert all(n in names for n in LIFECYCLE), (rid, names)
        firsts = [next(e[2] for e in mine if e[1] == n) for n in LIFECYCLE]
        assert firsts == sorted(firsts), (rid, names)
        a, dur = next((e[2], e[3]) for e in mine if e[1] == "serve.migrate")
        inside = {e[1] for e in ev if e[0] == "X" and a <= e[2]
                  and e[2] + e[3] <= a + dur}
        assert {"wr_build", "mr_fetch", "dma_flush"} <= inside, (rid,
                                                                 inside)
        pod = next(e for e in mine if e[1] == "serve.prefill")
        assert pod[4] in PREFILL_GIDS and pod[5]["tokens"] == \
            len(PROMPTS[rids.index(rid)])
    steps = [e for e in ev if e[1] == "serve.decode_step"]
    assert steps and all(e[4] in DECODE_GIDS and e[5]["active"] >= 1
                         for e in steps)


def test_a_datapath_tracer_records_only_the_stage_chain(gemma):
    model, params = gemma
    with trace.tracing() as t:
        _serve(model, params)
    names = {e[1] for e in t.events()}
    assert "post_send" in names and "poll_cq" in names
    assert not {n for n in names if n.startswith("serve.")
                or n in ("wr_build", "mr_fetch", "dma_flush")}


def test_clock_map_pairs_anchor_midpoints():
    # the profile runs twice as fast as the ring, 5 us ahead of it
    f = trace.clock_map([(100, 110), (1100, 1110)],
                        [(5000, 5010), (7000, 7010)])
    assert f(105) == 5005 and f(605) == 6005
    g = trace.clock_map([(100, 110)], [(5000, 5010)])
    assert g(1105) == 6005
    with pytest.raises(ValueError):
        trace.clock_map([(100, 110)], [])


def test_obs_trace_imports_and_anchors_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro.obs import trace\n"
            "t = trace.Tracer(detail=True); t.anchor()\n"
            "assert len(t.anchors()) == 1 and sys.modules['jax'] is None\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_anchors_map_a_ring_span_into_its_profiler_annotation(tmp_path):
    from jax._src.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr = trace.install(trace.Tracer())
        tr.anchor()
        time.sleep(0.02)
        with jax.profiler.TraceAnnotation("probe"):
            t0 = tr.now()
            time.sleep(0.005)
            tr.complete("probe", t0)
        time.sleep(0.02)
        tr.anchor()
        trace.uninstall()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    host = [ev for plane in data.planes for line in plane.lines
            for ev in line.events]
    anchors = sorted((e.start_ns, e.end_ns) for e in host
                     if e.name == trace.ANCHOR)
    (a, b), = [(e.start_ns, e.end_ns) for e in host if e.name == "probe"]
    to_prof = trace.clock_map(tr.anchors(), anchors)
    (r0, dur), = [(e[2], e[3]) for e in tr.events() if e[1] == "probe"]
    assert to_prof(r0) >= a - 50_000 and to_prof(r0 + dur) <= b + 50_000


def _module(compiled) -> str:
    return re.match(r"HloModule (\w+)", compiled.as_text()).group(1)


def test_device_programs_keep_the_names_the_readers_look_up(gemma):
    model, params = gemma
    fabric = verbs.Fabric(pods=2)
    eng = ServeEngine(model, params, max_batch=2, max_seq=32, fabric=fabric,
                      gid="pod1/dev0", service="serve/pod1/dev0",
                      page_tokens=8)
    pod = PrefillPod(model, params, fabric=fabric, gid="pod0/dev0",
                     decode_gids=["pod1/dev0"], max_seq=32, page_tokens=8)
    step = eng._paged_step.lower(
        params, jnp.zeros((2, 1), jnp.int32), jnp.asarray(eng.pool.table),
        jnp.zeros((2,), jnp.int32), eng.pool.regions()).compile()
    prefill = pod._prefill.lower(
        params, jnp.zeros((1, 16), jnp.int32),
        last_pos=jnp.asarray([3], jnp.int32)).compile()
    region = jnp.zeros((4, 2, 3), jnp.float32)
    offs = jnp.asarray([1, 2], jnp.int32)
    gather = wr_scatter_ops._gather.lower(region, offs, length=6).compile()
    scatter = wr_scatter_ops._scatter.lower(
        region, np.ones((2, 2, 3), np.float32), offs).compile()
    assert [_module(c) for c in (step, prefill, gather, scatter)] == \
        ["jit_step", "jit_prefill", "jit__gather", "jit__scatter"]
    pod.close()
    eng.close()
