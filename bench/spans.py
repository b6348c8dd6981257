"""The program's own spans and scopes, out of a profiler trace
(``.xplane.pb``).

``jax.profiler.ProfileData`` gives each event's name and times, but not
the stats on the event's metadata, where a device plane keeps each op's
``tf_op`` path: the ``jax.named_scope`` names the program put around
it. This module decodes the file itself, with a descriptor of the few
``xplane.proto`` fields it reads (their public field numbers), built at
run time.

``read(path)`` gives, within the ``bench.window`` span:

* ``spans``: per program span (a name starting ``fit.`` or ``host.``)
  on any host line, ``{"s": seconds, "n": count}``;
* ``scopes``: per device, the self time of its ops under each chain of
  the program's scopes, ``"hts.learner/per_env_grad"`` say; the drain's
  ops keep their own chains (``"hts.drain/..."``), and ops under no
  scope are ``"unscoped"``;
* ``idle_by_span``: the first device's idle seconds, split by the
  innermost program span around them (``"no span"`` where none is),
  whichever host thread opened it.

The sweeps are plain functions of (start, end, name) lists, so they can
be checked on hand-made events as well as on a recorded trace.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace

Event = Tuple[float, float, str]

PROGRAM_SPANS = ("fit.", "host.")
# the program's jax.named_scope names (repro.core.spans)
SCOPES = ("hts.rollout", "actor_forward", "env_step", "hts.learner",
          "per_env_grad", "grad_reduce", "optimizer", "hts.drain")
UNSCOPED = "unscoped"
NO_SPAN = "no span"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_.]*")


# ------------------------------------------------------------- decoding
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


def load(path: str) -> dict:
    """``{"devices": {plane: [(start_ns, end_ns, tf_op path), ...]},
    "host": [(start_ns, end_ns, name), ...]}``: every device's
    ``XLA Ops`` and every host line's events."""
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
        meta = {}
        for e in plane.event_metadata:
            label = e.value.name
            if is_device:
                label = next((_stat(s, stat_names) for s in e.value.stats
                              if s.metadata_id == tf_op), "")
            meta[e.key] = label
        out = []
        for line in plane.lines:
            if is_device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                # whole nanoseconds, as ProfileData (bench.trace) reads them
                s = float(line.timestamp_ns + ev.offset_ps // 1000)
                out.append((s, s + ev.duration_ps // 1000,
                            meta.get(ev.metadata_id, "")))
        if is_device:
            devices[plane.name] = out
        else:
            host.extend(out)
    return {"devices": devices, "host": host}


# ------------------------------------------------------------ reductions
def scope_chain(tf_op: str) -> str:
    """The program's scopes along an op's ``tf_op`` path, outermost
    first: ``'jit(body)/while/body/hts.learner/per_env_grad/vmap(jvp())
    /dot_general:'`` -> ``'hts.learner/per_env_grad'``. A fused op that
    names several paths (``a;b``) is read by its first."""
    found = []
    for w in _WORD.findall(tf_op.split(";")[0]):
        if w in SCOPES and w not in found:     # a fused op may repeat them
            found.append(w)
    return "/".join(found) if found else UNSCOPED


def window(planes: dict) -> Optional[Tuple[float, float]]:
    """The ``bench.window`` span on any host line; where the host tracer
    dropped it, the extent of everything traced."""
    spans = [(s, e) for s, e, n in planes["host"] if n == trace.WINDOW_SPAN]
    if spans:
        return spans[0]
    every = planes["host"] + [
        ev for ops in planes["devices"].values() for ev in ops]
    if not every:
        return None
    return min(s for s, _, _ in every), max(e for _, e, _ in every)


def span_totals(host: List[Event], lo: float, hi: float) -> dict:
    """Seconds and count of each program span inside [lo, hi]."""
    out: Dict[str, dict] = {}
    for s, e, n in host:
        if n.startswith(PROGRAM_SPANS) and e > lo and s < hi:
            t = out.setdefault(n, {"s": 0.0, "n": 0})
            t["s"] += (min(e, hi) - max(s, lo)) * 1e-9
            t["n"] += 1
    return out


def scope_times(ops: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Seconds of op self time (an op minus the ops nested in it) per
    scope chain, inside [lo, hi]."""
    named = [(max(s, lo), min(e, hi), scope_chain(n))
             for s, e, n in ops if e > lo and s < hi]
    return {k: v * 1e-9 for k, v in trace.self_times(named).items()}


def idle_split(idle: List[trace.Interval], spans: List[Event]
               ) -> Dict[str, float]:
    """Idle time under each innermost (shortest) span that covers it,
    ``NO_SPAN`` where none does; ``idle`` disjoint, in the spans' unit."""
    edges = [(s, 1, -1) for s, _ in idle] + [(e, 0, -1) for _, e in idle]
    edges += [(s, 3, i) for i, (s, _, _) in enumerate(spans)]
    edges += [(e, 2, i) for i, (_, e, _) in enumerate(spans)]
    edges.sort()
    out: Dict[str, float] = defaultdict(float)
    active, idle_on, t_prev = set(), False, None
    for t, kind, i in edges:
        if idle_on and t_prev is not None and t > t_prev:
            inner = min(active, default=None,
                        key=lambda k: spans[k][1] - spans[k][0])
            out[NO_SPAN if inner is None else spans[inner][2]] += t - t_prev
        if kind == 0:
            idle_on = False
        elif kind == 1:
            idle_on = True
        elif kind == 2:
            active.discard(i)
        else:
            active.add(i)
        t_prev = t
    return dict(out)


def reduce(planes: dict) -> dict:
    """``spans``, ``scopes`` and ``idle_by_span`` of loaded planes."""
    win = window(planes)
    if win is None:
        raise ValueError("trace holds no events")
    lo, hi = win
    if not planes["devices"]:
        raise ValueError("trace has no TPU device plane")
    first = planes["devices"][sorted(planes["devices"])[0]]
    idle = trace.gaps([(s, e) for s, e, _ in first], lo, hi)
    program = [(max(s, lo), min(e, hi), n) for s, e, n in planes["host"]
               if n.startswith(PROGRAM_SPANS) and e > lo and s < hi]
    return {
        "spans": span_totals(planes["host"], lo, hi),
        "scopes": {dev: scope_times(ops, lo, hi)
                   for dev, ops in sorted(planes["devices"].items())},
        "idle_by_span": {k: v * 1e-9
                         for k, v in idle_split(idle, program).items()},
    }


def read(path: str) -> dict:
    """``reduce(load(path))``."""
    return reduce(load(path))
