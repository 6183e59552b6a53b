"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time and idle share, device time
per program, and what the host was doing in each idle gap.

  * device planes are ``/device:TPU:<n>``; an operation's interval is
    an event of the plane's ``XLA Ops`` line, a program's an event of
    its ``XLA Modules`` line (``jit_<function>(<id>)``; the id is
    dropped);
  * busy time is the union of the operation intervals inside the
    window; the window is the host span ``bench.window`` the harness
    opens around the measured traffic;
  * each stretch of an idle gap is put down to the innermost harness
    span (``bench.<name>``) open on the host over it, or to ``other``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
LABELS = {"bench.wait": "arrival_wait", "bench.router_step": "dispatch",
          "bench.prefill_pod": "prefill", "bench.prefill": "prefill",
          "bench.migrate": "migrate", "bench.activate": "activate",
          "bench.decode": "decode", "bench.track": "harness"}


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: list[float]                 # per chip
    programs: dict[str, float]          # seconds, summed over chips
    idle_by_host: dict[str, float]      # seconds, mean over chips

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def program_s(self, *names: str) -> float:
        return sum(self.programs.get(n, 0.0) for n in names)


def program_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int):
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def segments(spans: list[tuple[int, int, str]]) -> list[tuple]:
    """The host's timeline as (start, end, label) pieces, each labelled by
    the innermost harness span open over it (spans nest on the one
    thread that drives the cluster)."""
    points = sorted([(a, 1, n) for a, _, n in spans]
                    + [(b, 0, n) for _, b, n in spans])
    out, stack, last = [], [], None
    for t, is_start, name in points:
        if last is not None and t > last:
            out.append((last, t, LABELS.get(stack[-1], stack[-1])
                        if stack else "other"))
        if is_start:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        last = t
    return out


def attribute(gap_list, segs) -> dict[str, float]:
    """Seconds of each idle gap under each host label ("other" where no
    harness span was open)."""
    out: dict[str, float] = {}
    j = 0
    for a, b in gap_list:
        covered = 0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(a, segs[k][0]), min(b, segs[k][1])
            if hi > lo:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + (hi - lo) / 1e9
                covered += hi - lo
            k += 1
        if b - a > covered:
            out["other"] = out.get("other", 0.0) + (b - a - covered) / 1e9
    return out


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str):
    from jax._src.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(data) -> Summary | None:
    """Summary of a trace (`load`'s ProfileData), or None when no
    operation ran on a device inside the window."""
    window = None
    host: list[tuple[int, int, str]] = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif name.startswith("bench."):
                    host.append((ev.start_ns, ev.end_ns, name))
    if not devices:
        return None
    per_chip_ops, per_chip_mods = [], []
    for plane in devices:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(ev.start_ns, ev.end_ns, ev.name)
                       for ev in line.events]
            elif line.name == MODULES_LINE:
                mods = [(ev.start_ns, ev.end_ns, ev.name)
                        for ev in line.events]
        per_chip_ops.append(ops)
        per_chip_mods.append(mods)
    if window is None:
        every = [e for ops in per_chip_ops for e in ops]
        if not every:
            return None
        window = (min(e[0] for e in every), max(e[1] for e in every))
    lo, hi = window
    segs = segments(host)
    busy_s, programs, idle = [], {}, {}
    for ops, mods in zip(per_chip_ops, per_chip_mods):
        busy = merge(clip([(a, b) for a, b, _ in ops], lo, hi))
        busy_s.append(sum(b - a for a, b in busy) / 1e9)
        for a, b, name in mods:
            if b > lo and a < hi:
                n = program_name(name)
                programs[n] = programs.get(n, 0.0) + \
                    (min(b, hi) - max(a, lo)) / 1e9
        for label, sec in attribute(gaps(busy, lo, hi), segs).items():
            idle[label] = idle.get(label, 0.0) + sec
    n = len(devices)
    if not any(busy_s):
        return None
    return Summary((hi - lo) / 1e9, busy_s, programs,
                   {k: v / n for k, v in idle.items()})


def breakdown(s: Summary, top: int = 10) -> dict:
    ops = sorted(s.programs.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(s.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
