"""Trace reduction: busy and idle time, device time per program, and the
host span each idle gap falls in, on a hand-written trace whose answers
are known, and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import device_trace

DATA = Path(__file__).parent / "data"


def _event(mid, off_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {off_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}")


def _plane(pid, name, lines, names):
    body = "".join(
        f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0 '
        + " ".join(_event(*e) for e in evs) + " }\n"
        for i, (ln, evs) in enumerate(lines))
    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: '
                   f'"{v}" }} }}\n' for k, v in names.items())
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def synthetic():
    # window 0..100 us; ops busy 10-30 and 25-40 (union 30 us) and 60-70;
    # host: decode 0-45 around the first ops, waiting 40-60 (the newer
    # span, so 40-45 is waiting), the router 70-100: idle gaps 0-10 under
    # decode, 40-60 under waiting, 70-100 under the router
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [(1, 10, 30), (2, 60, 10)]),
        ("XLA Ops", [(3, 10, 20), (3, 25, 15), (4, 60, 10)])],
        {1: "jit_step(7)", 2: "jit_prefill(3)", 3: "fusion.1",
         4: "fusion.2"})
    host = _plane(2, "/host:CPU", [
        ("python", [(1, 0, 100), (2, 0, 45), (3, 40, 20), (4, 70, 30)])],
        {1: "bench.window", 2: "bench.decode", 3: "bench.wait",
         4: "bench.router_step"})
    from jax._src.profiler import ProfileData
    return ProfileData.from_text_proto(dev + host)


def test_reduction_of_a_known_trace():
    s = device_trace.reduce(synthetic())
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == [pytest.approx(40e-6)]
    assert s.programs == {"jit_step": pytest.approx(30e-6),
                          "jit_prefill": pytest.approx(10e-6)}
    assert s.idle_by_host == {"decode": pytest.approx(10e-6),
                              "arrival_wait": pytest.approx(20e-6),
                              "dispatch": pytest.approx(30e-6)}
    b = device_trace.breakdown(s)
    assert b["device_ops"][0][0] == "jit_step"
    assert b["idle_gaps"][0][0] == "dispatch"


def test_no_device_plane_reads_nothing():
    from jax._src.profiler import ProfileData
    host = _plane(2, "/host:CPU", [("python", [(1, 0, 100)])],
                  {1: "bench.window"})
    assert device_trace.reduce(ProfileData.from_text_proto(host)) is None


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e around two jitted programs, with
    the harness's host spans: every device second lies inside the
    window and each program is found under its jit name."""
    path = DATA / "v5e_probe.xplane.pb"
    s = device_trace.reduce(device_trace.load(str(path)))
    assert s.chips == 1
    assert 0 < s.busy_s[0] < s.window_s
    assert set(s.programs) >= {"jit_probe_matmul", "jit_probe_small"}
    assert sum(s.idle_by_host.values()) == pytest.approx(
        s.window_s - s.busy_s[0], rel=1e-6)
    assert s.idle_by_host.get("arrival_wait", 0) > 0
