"""The program's own spans (a `repro.obs.trace` tracer with ``detail``
on), reduced to the per-layer numbers they give, and laid over the
device trace through the tracer's clock anchors.

Ring events are the tracer's tuples ``(phase, name, t0_ns, dur_ns, tid,
args)`` on its clock (``perf_counter_ns``); a window is ``(lo, hi)`` on
the same clock. Requests are told apart by the ``rid`` in each event's
args.

  * `admit_wait_ms`: median over requests submitted in the window of
    ``serve.admit`` - ``serve.submit`` (the time in the router's queue);
  * `child_ms_per_migration`: time of one child span (``mr_fetch``: the
    source gather and its device->host copy; ``dma_flush``: the landing)
    inside the window's ``serve.migrate`` spans, over their count;
  * `activation_wait_ms`: median over requests of ``serve.activated`` -
    the end of their last ``serve.activate`` (landed pages waiting for
    the decode pod to bind them);
  * `copy_idle_share`: share of the traced window in which no operation
    runs on the chip while the host is inside an ``mr_fetch`` or
    ``dma_flush`` under ``serve.migrate``, with the ring mapped onto the
    profile through the anchors (mean over chips, %).

None wherever there is nothing to read: a program without these spans
gives an empty ring, and every function reads None from it.
"""
from __future__ import annotations

import statistics

from bench import device_trace
from repro.obs import trace

MIGRATE = "serve.migrate"
COPY_PHASES = ("mr_fetch", "dma_flush")


def spans(events, name: str) -> list[tuple[int, int, dict]]:
    return [(e[2], e[2] + e[3], e[5]) for e in events
            if e[0] == "X" and e[1] == name]


def instants(events, name: str) -> dict:
    """rid -> time of the last `name` instant of that request."""
    return {e[5]["rid"]: e[2] for e in events
            if e[0] == "i" and e[1] == name}


def _inside(children, parents) -> list[tuple[int, int]]:
    """The parts of `children` that lie inside some parent interval."""
    out = []
    for a, b, _ in parents:
        out += device_trace.clip([(c, d) for c, d, _ in children], a, b)
    return device_trace.merge(out)


def _migrations(events, lo: int, hi: int):
    return [s for s in spans(events, MIGRATE) if lo <= s[0] < hi]


def admit_wait_ms(events, lo: int, hi: int) -> float | None:
    sub = instants(events, "serve.submit")
    adm = instants(events, "serve.admit")
    waits = [adm[r] - t for r, t in sub.items() if lo <= t < hi and r in adm]
    return statistics.median(waits) / 1e6 if waits else None


def child_ms_per_migration(events, child: str, lo: int,
                           hi: int) -> float | None:
    mig = _migrations(events, lo, hi)
    if not mig:
        return None
    inner = _inside(spans(events, child), mig)
    return sum(b - a for a, b in inner) / 1e6 / len(mig)


def activation_wait_ms(events, lo: int, hi: int) -> float | None:
    done = instants(events, "serve.activated")
    last: dict = {}
    for a, b, args in spans(events, "serve.activate"):
        if args["rid"] in done and b <= done[args["rid"]]:
            last[args["rid"]] = max(b, last.get(args["rid"], b))
    waits = [done[r] - b for r, b in last.items() if lo <= done[r] < hi]
    return statistics.median(waits) / 1e6 if waits else None


# -- the device trace ------------------------------------------------------

def profile_anchors(data) -> list[tuple[int, int]]:
    """(start, end) of each anchor annotation on the profile's host
    planes, oldest first."""
    return sorted((ev.start_ns, ev.end_ns) for plane in data.planes
                  if not device_trace.DEVICE_PLANE.match(plane.name)
                  for line in plane.lines for ev in line.events
                  if ev.name == trace.ANCHOR)


def window(data) -> tuple[int, int] | None:
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == device_trace.WINDOW:
                    return ev.start_ns, ev.end_ns
    return None


def busy(data, lo: int, hi: int) -> list[list[tuple[int, int]]]:
    """Per chip, the merged intervals in which an operation runs."""
    return [device_trace.merge(device_trace.clip(
        [(ev.start_ns, ev.end_ns) for line in plane.lines
         if line.name == device_trace.OPS_LINE for ev in line.events],
        lo, hi))
        for plane in data.planes
        if device_trace.DEVICE_PLANE.match(plane.name)]


def idle_under(busy_chip, intervals, lo: int, hi: int) -> int:
    """Time in `intervals` (merged) in which the chip runs nothing."""
    idle = device_trace.gaps(busy_chip, lo, hi)
    total = 0
    for a, b in device_trace.merge(device_trace.clip(intervals, lo, hi)):
        for c, d in device_trace.clip(idle, a, b):
            total += d - c
    return total


def copy_idle_share(events, data) -> float | None:
    ring = [(e[2], e[2] + e[3]) for e in events if e[1] == trace.ANCHOR]
    prof = profile_anchors(data)
    win = window(data)
    if not ring or len(ring) != len(prof) or win is None:
        return None
    to_prof = trace.clock_map(ring, prof)
    lo, hi = win
    copies = []
    for name in COPY_PHASES:
        copies += spans(events, name)
    inner = _inside(copies, spans(events, MIGRATE))
    mapped = [(to_prof(a), to_prof(b)) for a, b in inner]
    chips = busy(data, lo, hi)
    if not chips or not inner:
        return None
    idle = [idle_under(c, mapped, lo, hi) for c in chips]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
