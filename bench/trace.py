"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run (a loop's op spans its body's ops) and ``XLA
Modules`` one per program run. The host plane ``/host:CPU`` has a line
per thread; the ``python`` line holds the benchmark's own
``TraceAnnotation`` spans, and the window is the span named
``bench.window``. All times are nanoseconds on one clock.

The reductions are plain functions of (start, end, name) lists, so they
can be checked on hand-made intervals as well as on a recorded trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench.window"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


# ------------------------------------------------------------ intervals
def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> float:
    """Length of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total(a) - covered


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between busy intervals."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Per name, the time its events ran minus the time of events nested
    inside them (a loop op's body ops, say)."""
    evs = sorted(events, key=lambda x: (x[0], -(x[1] - x[0])))
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [end, name, child_time, start]
    def pop():
        end, name, child, start = stack.pop()
        out[name] += (end - start) - child
        if stack:
            stack[-1][2] += end - start
    for s, e, name in evs:
        while stack and stack[-1][0] <= s:
            pop()
        stack.append([e, name, 0.0, s])
    while stack:
        pop()
    return dict(out)


def op_name(event_name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return event_name.split(" = ")[0].lstrip("%").strip()


def is_collective(event_name: str) -> bool:
    """Whether an op event (its full HLO text) is a collective."""
    return any(c in event_name for c in COLLECTIVES)


# ---------------------------------------------------------------- xplane
def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line):
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def load(path: str) -> dict:
    """The planes this module reads, as plain lists:
    ``{"devices": {name: {"ops": [...], "modules": [...]}},
       "host": {thread: [...]}}`` of (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {l.name: _events(l) for l in plane.lines}
            devices[plane.name] = {"ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])}
        elif plane.name == "/host:CPU":
            for l in plane.lines:
                host[l.name] = _events(l)
    return {"devices": devices, "host": host}


def window(planes: dict) -> Optional[Interval]:
    """The ``bench.window`` span; where the host tracer dropped it (its
    buffer holds a bounded number of events), the extent of everything
    traced, since the trace wraps the window and nothing else."""
    spans = [(s, e) for s, e, n in planes["host"].get("python", [])
             if n == WINDOW_SPAN]
    if spans:
        return spans[0]
    every = [ev for evs in planes["host"].values() for ev in evs] + [
        ev for d in planes["devices"].values() for ev in d["ops"]]
    if not every:
        return None
    return min(s for s, _, _ in every), max(e for _, e, _ in every)


def reduce(planes: dict, top: int = 10) -> dict:
    """Busy and idle time, per-program time, exposed collective time and
    the breakdown, within the ``bench.window`` span.

    Returns ``window_s``, ``busy_s`` (mean over devices), per device
    ``busy_s``, ``modules`` (name -> seconds) and ``collective_exposed_s``
    (collective op time with no other op running on that device), plus
    ``device_ops`` (top ops by self time, summed over devices) and
    ``idle_gaps`` (the longest idle stretches of device 0, named by the
    innermost benchmark or JAX host span around their midpoint)."""
    win = window(planes)
    if win is None:
        raise ValueError("trace holds no events")
    lo, hi = win
    per_device = {}
    op_self: Dict[str, float] = defaultdict(float)
    for dev, lines in sorted(planes["devices"].items()):
        ops = clip([(s, e) for s, e, _ in lines["ops"]], lo, hi)
        named = [(max(s, lo), min(e, hi), n)
                 for s, e, n in lines["ops"] if e > lo and s < hi]
        busy = union(ops)
        coll = [(s, e) for s, e, n in named if is_collective(n)]
        other = [(s, e) for s, e, n in named if not is_collective(n)]
        named = [(s, e, op_name(n)) for s, e, n in named]
        modules: Dict[str, float] = defaultdict(float)
        for s, e, n in lines["modules"]:
            if e > lo and s < hi:
                modules[n.split("(")[0]] += min(e, hi) - max(s, lo)
        for n, t in self_times(named).items():
            op_self[n] += t
        per_device[dev] = {
            "busy_s": total(busy) * 1e-9,
            "modules_s": {k: v * 1e-9 for k, v in modules.items()},
            "collective_exposed_s": subtract(coll, other) * 1e-9,
            "busy": busy,
        }
    if not per_device:
        raise ValueError("trace has no TPU device plane")
    first = per_device[sorted(per_device)[0]]
    idle = sorted(gaps(first["busy"], lo, hi), key=lambda g: g[0] - g[1])
    python = planes["host"].get("python", [])
    idle_named = []
    for s, e in idle[:top]:
        mid = (s + e) / 2
        around = [(ee - ss, n) for ss, ee, n in python
                  if ss <= mid <= ee and n != WINDOW_SPAN]
        idle_named.append([min(around)[1] if around else "host:no span",
                           (e - s) * 1e-9])
    ops_sorted = sorted(op_self.items(), key=lambda kv: -kv[1])[:top]
    for d in per_device.values():
        del d["busy"]
    busy_mean = sum(d["busy_s"] for d in per_device.values()) / len(
        per_device)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_mean,
            "devices": per_device,
            "device_ops": [[n, t * 1e-9] for n, t in ops_sorted],
            "idle_gaps": idle_named}
