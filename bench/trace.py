"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run (a loop's op spans its body's ops) and ``XLA
Modules`` one per program run. The host plane ``/host:CPU`` has a line
per thread, named by the thread; the Python threads' lines may all be
named ``python3``, so the host's events are read from every line as one
list. The window is the ``bench.window`` span, and the program's own
spans (``PROGRAM_SPANS``) may lie on any thread's line. All times are
nanoseconds on one clock.

``load`` decodes the file itself, with a descriptor of the few
``xplane.proto`` fields it reads (their public field numbers), built at
run time: ``jax.profiler.ProfileData`` gives each event's name and
times, but not the stats on the event's metadata, where a device plane
keeps each op's ``tf_op`` path (the ``jax.named_scope`` names the
program put around it, which ``bench.spans`` reads).

The reductions are plain functions of (start, end, name) lists, so they
can be checked on hand-made intervals as well as on a recorded trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
Event = Tuple[float, float, str]

WINDOW_SPAN = "bench.window"
# the program's host spans (repro.core.spans): the fit loop, the host
# runtime's coordinator and threads
PROGRAM_SPANS = ("fit.", "host.")
NO_SPAN = "host:no span"
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


def _messages():
    """XSpace, built from a descriptor of the fields read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package="bench_xplane")
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    shapes = {
        "XSpace": [("planes", 1, many, "XPlane")],
        # maps are repeated key/value entries on the wire
        "XPlane": [("name", 2, one, F.TYPE_STRING),
                   ("lines", 3, many, "XLine"),
                   ("event_metadata", 4, many, "EventEntry"),
                   ("stat_metadata", 5, many, "StatEntry")],
        "EventEntry": [("key", 1, one, F.TYPE_INT64),
                       ("value", 2, one, "XEventMetadata")],
        "StatEntry": [("key", 1, one, F.TYPE_INT64),
                      ("value", 2, one, "XStatMetadata")],
        "XLine": [("name", 2, one, F.TYPE_STRING),
                  ("timestamp_ns", 3, one, F.TYPE_INT64),
                  ("events", 4, many, "XEvent")],
        "XEvent": [("metadata_id", 1, one, F.TYPE_INT64),
                   ("offset_ps", 2, one, F.TYPE_INT64),
                   ("duration_ps", 3, one, F.TYPE_INT64)],
        "XEventMetadata": [("name", 2, one, F.TYPE_STRING),
                           ("stats", 5, many, "XStat")],
        "XStat": [("metadata_id", 1, one, F.TYPE_INT64),
                  ("str_value", 5, one, F.TYPE_STRING),
                  ("ref_value", 7, one, F.TYPE_UINT64)],
        "XStatMetadata": [("name", 2, one, F.TYPE_STRING)],
    }
    for name, fields in shapes.items():
        m = fd.message_type.add(name=name)
        for fname, number, label, typ in fields:
            f = m.field.add(name=fname, number=number, label=label)
            if isinstance(typ, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat(stat, names: Dict[int, str]) -> str:
    """A string stat's value, held inline or interned by reference."""
    return stat.str_value or names.get(stat.ref_value, "")


def _events(line, meta: dict) -> List[Tuple[float, float, str, str]]:
    """(start_ns, end_ns, name, tf_op) of a line's events, in whole
    nanoseconds."""
    out = []
    for ev in line.events:
        s = float(line.timestamp_ns + ev.offset_ps // 1000)
        out.append((s, s + ev.duration_ps // 1000) + meta[ev.metadata_id])
    return out


def load(path: str) -> dict:
    """The planes this module and ``bench.spans`` read, as plain lists:
    ``{"devices": {name: {"ops": [(start, end, hlo, tf_op), ...],
    "modules": [(start, end, name), ...]}}, "host": [(start, end,
    name), ...]}``, where ``hlo`` is the op's HLO text, ``tf_op`` the
    path of scopes it was traced under, and the host's events come from
    every thread's line."""
    space = _messages()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, host = {}, []
    for plane in space.planes:
        is_device = plane.name.startswith("/device:TPU:")
        if not is_device and plane.name != "/host:CPU":
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"),
                     None)
        meta = defaultdict(lambda: ("", ""))
        for e in plane.event_metadata:
            meta[e.key] = (e.value.name, next(
                (_stat(s, stat_names) for s in e.value.stats
                 if s.metadata_id == tf_op), ""))
        if is_device:
            lines = {l.name: _events(l, meta) for l in plane.lines
                     if l.name in ("XLA Ops", "XLA Modules")}
            devices[plane.name] = {
                "ops": lines.get("XLA Ops", []),
                "modules": [ev[:3] for ev in lines.get("XLA Modules", [])]}
        else:
            for line in plane.lines:
                host.extend(ev[:3] for ev in _events(line, meta))
    return {"devices": devices, "host": host}


def window(planes: dict) -> Optional[Interval]:
    """The ``bench.window`` span on any host line; where the host tracer
    dropped it (its buffer holds a bounded number of events), the extent
    of everything traced, since the trace wraps the window and nothing
    else."""
    spans = [(s, e) for s, e, n in planes["host"] if n == WINDOW_SPAN]
    if spans:
        return spans[0]
    every = [ev[:2] for ev in planes["host"]] + [
        ev[:2] for d in planes["devices"].values() for ev in d["ops"]]
    if not every:
        return None
    return min(s for s, _ in every), max(e for _, e in every)


def program_spans(host: List[Event], lo: float, hi: float) -> List[Event]:
    """The program's own spans (``PROGRAM_SPANS``) on any host line,
    clipped to [lo, hi]."""
    return [(max(s, lo), min(e, hi), n) for s, e, n in host
            if n.startswith(PROGRAM_SPANS) and e > lo and s < hi]


def idle_split(idle: List[Interval], spans: List[Event]
               ) -> List[Dict[str, float]]:
    """For each idle stretch, its time under each innermost (shortest)
    span that covers it, ``NO_SPAN`` where none does; ``idle`` disjoint
    and sorted, in the spans' unit."""
    edges = [(s, 1, g) for g, (s, _) in enumerate(idle)]
    edges += [(e, 0, g) for g, (_, e) in enumerate(idle)]
    edges += [(s, 3, i) for i, (s, _, _) in enumerate(spans)]
    edges += [(e, 2, i) for i, (_, e, _) in enumerate(spans)]
    edges.sort()
    out: List[Dict[str, float]] = [defaultdict(float) for _ in idle]
    active, gap, t_prev = set(), None, None
    for t, kind, i in edges:
        if gap is not None and t > t_prev:
            inner = min(active, default=None,
                        key=lambda k: spans[k][1] - spans[k][0])
            out[gap][NO_SPAN if inner is None else spans[inner][2]] += (
                t - t_prev)
        if kind == 0:
            gap = None
        elif kind == 1:
            gap = i
        elif kind == 2:
            active.discard(i)
        else:
            active.add(i)
        t_prev = t
    return [dict(d) for d in out]


def reduce(planes: dict, top: int = 10) -> dict:
    """Busy and idle time, per-program time, exposed collective time and
    the breakdown, within the ``bench.window`` span.

    Returns ``window_s``, ``busy_s`` (mean over devices), per device
    ``busy_s``, ``modules`` (name -> seconds) and ``collective_exposed_s``
    (collective op time with no other op running on that device), plus
    ``device_ops`` (top ops by self time, summed over devices),
    ``idle_by_span`` (device 0's idle seconds, each moment under the
    innermost program span around it on any thread's line, ``NO_SPAN``
    where none is) and ``idle_gaps`` (device 0's longest idle stretches,
    each named by the span that holds most of it)."""
    win = window(planes)
    if win is None:
        raise ValueError("trace holds no events")
    lo, hi = win
    per_device = {}
    op_self: Dict[str, float] = defaultdict(float)
    for dev, lines in sorted(planes["devices"].items()):
        ops = clip([(s, e) for s, e, _, _ in lines["ops"]], lo, hi)
        named = [(max(s, lo), min(e, hi), n)
                 for s, e, n, _ in lines["ops"] if e > lo and s < hi]
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
    idle = gaps(first["busy"], lo, hi)
    split = idle_split(idle, program_spans(planes["host"], lo, hi))
    by_span: Dict[str, float] = defaultdict(float)
    for part in split:
        for name, t in part.items():
            by_span[name] += t
    longest = sorted(zip(idle, split), key=lambda g: g[0][0] - g[0][1])
    idle_named = [[max(part, key=part.get), (e - s) * 1e-9]
                  for (s, e), part in longest[:top]]
    ops_sorted = sorted(op_self.items(), key=lambda kv: -kv[1])[:top]
    for d in per_device.values():
        del d["busy"]
    busy_mean = sum(d["busy_s"] for d in per_device.values()) / len(
        per_device)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_mean,
            "devices": per_device,
            "device_ops": [[n, t * 1e-9] for n, t in ops_sorted],
            "idle_gaps": idle_named,
            "idle_by_span": {k: v * 1e-9 for k, v in by_span.items()}}
