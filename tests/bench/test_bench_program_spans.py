"""The program's spans reduced to per-layer numbers, on hand-built rings
and a hand-written profile whose answers are known."""
import pytest

from bench import program_spans as ps
from repro.obs import trace

from test_bench_trace import _plane


def X(name, t0, t1, tid="datapath", **args):
    return ("X", name, t0, t1 - t0, tid, args)


def I(name, t, **args):
    return ("i", name, t, 0, "router", args)


def test_admit_wait_is_the_median_over_requests_submitted_in_the_window():
    ring = [I("serve.submit", 1000, rid=0), I("serve.submit", 2000, rid=1),
            I("serve.admit", 4000, rid=0, decode="g"),
            I("serve.admit", 8000, rid=1, decode="g"),
            I("serve.submit", 50_000, rid=2),       # after the window
            I("serve.admit", 90_000, rid=2, decode="g")]
    assert ps.admit_wait_ms(ring, 0, 40_000) == pytest.approx(4500e-6)


def test_fetch_and_land_per_migration_count_only_time_inside_migrate():
    ring = [X("serve.migrate", 10_000, 20_000, rid=0),
            X("serve.migrate", 30_000, 40_000, rid=1),
            X("serve.migrate", 50_000, 60_000, rid=2),   # after the window
            X("mr_fetch", 11_000, 13_000), X("mr_fetch", 31_000, 35_000),
            X("mr_fetch", 25_000, 26_000),               # outside migrate
            X("mr_fetch", 51_000, 59_000),
            X("dma_flush", 15_000, 19_000)]
    assert ps.child_ms_per_migration(ring, "mr_fetch", 0, 45_000) == \
        pytest.approx(3000e-6)
    assert ps.child_ms_per_migration(ring, "dma_flush", 0, 45_000) == \
        pytest.approx(2000e-6)
    assert ps.child_ms_per_migration([], "dma_flush", 0, 45_000) is None


def test_activation_wait_runs_from_the_last_activate_attempt():
    ring = [X("serve.activate", 20_000, 21_000, rid=0),
            X("serve.activate", 22_000, 23_000, rid=0),   # the replay
            I("serve.activated", 26_000, rid=0, slot=0),
            X("serve.activate", 30_000, 30_500, rid=1),
            I("serve.activated", 31_500, rid=1, slot=1),
            X("serve.activate", 60_000, 60_500, rid=2),
            I("serve.activated", 61_000, rid=2, slot=0)]  # after
    assert ps.activation_wait_ms(ring, 0, 40_000) == pytest.approx(2000e-6)
    assert ps.activation_wait_ms(ring[:1], 0, 40_000) is None


def _profile():
    # window 0-100 us; anchors at 10-12 and 90-92 us; the chip busy 20-40
    # and 60-70 us
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Ops", [(1, 20, 20), (1, 60, 10)])], {1: "fusion.1"})
    host = _plane(2, "/host:CPU", [
        ("python", [(1, 0, 100), (2, 10, 2), (2, 90, 2)])],
        {1: "bench.window", 2: trace.ANCHOR})
    from jax._src.profiler import ProfileData
    return ProfileData.from_text_proto(dev + host)


def _ring(p_us):
    """A profile time in us on a ring clock that runs at half the
    profile's rate from 1 ms: offset and drift both."""
    return int(p_us * 1000 / 2 + 1_000_000)


def test_anchor_map_and_copy_idle_share_are_exact():
    data = _profile()
    assert ps.profile_anchors(data) == [(10_000, 12_000), (90_000, 92_000)]
    r = _ring
    ring = [X(trace.ANCHOR, r(10), r(12), tid="obs"),
            X(trace.ANCHOR, r(90), r(92), tid="obs"),
            X("serve.migrate", r(15), r(80), rid=0),
            X("mr_fetch", r(30), r(50)),        # idle 40-50 under it
            X("dma_flush", r(55), r(75)),       # idle 55-60 and 70-75
            X("mr_fetch", r(82), r(88))]        # outside serve.migrate
    to_prof = trace.clock_map([(r(10), r(12)), (r(90), r(92))],
                              ps.profile_anchors(data))
    assert to_prof(r(30)) == pytest.approx(30_000)
    assert to_prof(r(75)) == pytest.approx(75_000)
    assert ps.copy_idle_share(ring, data) == pytest.approx(20.0)
    # no anchors in the ring: nothing to map
    assert ps.copy_idle_share(ring[2:], data) is None


def test_idle_under_counts_each_idle_stretch_once():
    busy = [(20, 40), (60, 70)]
    assert ps.idle_under(busy, [(30, 50), (45, 65)], 0, 100) == 20
    assert ps.idle_under(busy, [(20, 40)], 0, 100) == 0
