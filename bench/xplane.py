"""Reduction of one profiler capture (`.xplane.pb`) to the events the metrics read.

`jax.profiler.ProfileData` reads the capture. Three kinds of event are kept,
all on one clock (nanoseconds):

* device ops: the "XLA Ops" line of each TPU plane, one event per op run,
  named by the op's HLO text (a loop's event spans the ops it runs);
* device programs: the "XLA Modules" line, one event per program run,
  named `<jitted function>(<id>)`;
* host spans: `TraceAnnotation` events of the benchmark's own names.

Busy time is the union of the op intervals of a device, clipped to the
window; idle gaps are what that union leaves out, each named by the host
span that was open at its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

HOST_SPANS = ("submit", "engine.step", "generator.sleep", "admit+prefill",
              "decode_dispatch")


@dataclasses.dataclass
class Capture:
    ops: Dict[str, List[Event]]       # device plane -> op events
    modules: Dict[str, List[Event]]   # device plane -> program events
    host: List[Event]                 # benchmark host spans


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Capture:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = []
                for ev in line.events:
                    s = int(ev.start_ns)
                    evs.append((ev.name, s, s + int(ev.duration_ns)))
                (ops if line.name == "XLA Ops" else modules)[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        s = int(ev.start_ns)
                        host.append((ev.name, s, s + int(ev.duration_ns)))
    return Capture(ops, modules, host)


def merge(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Sorted, disjoint union of intervals, clipped to [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event], lo: int, hi: int) -> int:
    return sum(e - s for s, e in merge(((s, e) for _, s, e in events), lo, hi))


def gaps(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals of [lo, hi] in which no event runs."""
    out, t = [], lo
    for s, e in merge(((s, e) for _, s, e in events), lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def inside(events: Sequence[Event], spans: Sequence[Event]) -> List[Event]:
    """Events that lie inside one of `spans` (both sorted by start)."""
    out, j = [], 0
    spans = sorted(spans, key=lambda x: x[1])
    for ev in sorted(events, key=lambda x: x[1]):
        while j < len(spans) and spans[j][2] < ev[1]:
            j += 1
        if j < len(spans) and spans[j][1] <= ev[1] and ev[2] <= spans[j][2]:
            out.append(ev)
    return out


def centred_in(events: Sequence[Event], spans: Sequence[Event]
               ) -> List[Event]:
    """Events whose midpoint lies inside one of `spans`: a device event
    belongs to the host span it ran in, whatever small offset lies
    between the host's and the device's clocks in the capture."""
    starts = sorted(s for _, s, _ in spans)
    ends = {s: e for _, s, e in spans}
    out = []
    for ev in events:
        mid = (ev[1] + ev[2]) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= ends[starts[i]]:
            out.append(ev)
    return out


def programs(cap: Capture, plane: str, prefix: str) -> List[Event]:
    """Runs of the jitted function `prefix` on one device plane."""
    return [m for m in cap.modules.get(plane, ())
            if m[0].split("(")[0] == prefix]


def host_span_at(cap: Capture, t: int) -> str:
    """The innermost benchmark host span open at time t, else 'none'."""
    best = None
    for name, s, e in cap.host:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "none"


# an op that runs other ops of the same line (a loop, a call): its time is
# theirs, so the breakdown leaves it out
_CONTAINER = re.compile(r"%(while|conditional|call)[.\w-]* = ")


def breakdown(cap: Capture, plane: str, lo: int, hi: int, n: int = 10
              ) -> Dict[str, List]:
    """The n device ops that took most time, and the n longest idle gaps
    named by what the host was doing; seconds, unrounded."""
    per_op: Dict[str, int] = {}
    for name, s, e in cap.ops.get(plane, ()):
        if _CONTAINER.match(name):
            continue
        s, e = max(s, lo), min(e, hi)
        if e > s:
            per_op[name] = per_op.get(name, 0) + e - s
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:n]
    idle = sorted(gaps(cap.ops.get(plane, ()), lo, hi),
                  key=lambda g: g[0] - g[1])[:n]
    return {
        "device_ops": [[k, v / 1e9] for k, v in top],
        "idle_gaps": [[host_span_at(cap, (s + e) // 2), (e - s) / 1e9]
                      for s, e in idle],
    }


def window_ns(cap: Capture) -> Tuple[int, int]:
    """The traced window: from the first to the last event of any kind."""
    starts = [e[1] for evs in cap.ops.values() for e in evs] + \
        [e[1] for e in cap.host]
    ends = [e[2] for evs in cap.ops.values() for e in evs] + \
        [e[2] for e in cap.host]
    return min(starts), max(ends)
