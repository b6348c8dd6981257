"""The program's own spans and scopes, out of a profiler trace
(``.xplane.pb``) as ``bench.trace.load`` reads it.

``reduce(planes)`` gives, within the ``bench.window`` span:

* ``spans``: per program span (a name starting ``fit.`` or ``host.``)
  on any host line, ``{"s": seconds, "n": count}``;
* ``scopes``: per device, the self time of its ops under each chain of
  the program's scopes (its ``tf_op`` path), ``"hts.learner/per_env_grad"``
  say; the drain's ops keep their own chains (``"hts.drain/..."``), and
  ops under no scope are ``"unscoped"``.

The device's idle time by span is ``bench.trace.reduce``'s. The
reductions are plain functions of (start, end, name) lists, so they can
be checked on hand-made events as well as on a recorded trace.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

from bench import trace
# the program's jax.named_scope names: a scope it adds there is read here
from repro.core.spans import SCOPES

Event = trace.Event
UNSCOPED = "unscoped"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_.]*")


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


def span_totals(host: List[Event], lo: float, hi: float) -> dict:
    """Seconds and count of each program span inside [lo, hi]."""
    out: Dict[str, dict] = {}
    for s, e, n in trace.program_spans(host, lo, hi):
        t = out.setdefault(n, {"s": 0.0, "n": 0})
        t["s"] += (e - s) * 1e-9
        t["n"] += 1
    return out


def scope_times(ops: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Seconds of op self time (an op minus the ops nested in it) per
    scope chain, inside [lo, hi]."""
    named = [(max(s, lo), min(e, hi), scope_chain(n))
             for s, e, n in ops if e > lo and s < hi]
    return {k: v * 1e-9 for k, v in trace.self_times(named).items()}


def reduce(planes: dict) -> dict:
    """``spans`` and ``scopes`` of planes loaded by ``trace.load``."""
    win = trace.window(planes)
    if win is None:
        raise ValueError("trace holds no events")
    lo, hi = win
    return {
        "spans": span_totals(planes["host"], lo, hi),
        "scopes": {dev: scope_times([(s, e, tf) for s, e, _, tf in d["ops"]],
                                    lo, hi)
                   for dev, d in sorted(planes["devices"].items())},
    }


def read(path: str) -> dict:
    """``reduce`` of the trace file at ``path``."""
    return reduce(trace.load(path))


def scope_ms_per_interval(record: dict, chain: str) -> Optional[float]:
    """Milliseconds of op self time per interval under every scope chain
    that begins with ``chain`` (``hts.rollout`` counts
    ``hts.rollout/env_step``), the largest over devices;
    None where the run was not traced or no op ran under it."""
    tr = record.get("trace")
    if not tr or "scopes" not in tr or not record.get("intervals"):
        return None
    per = [sum(t for c, t in scopes.items()
               if c == chain or c.startswith(chain + "/"))
           for scopes in tr["scopes"].values()]
    if not any(per):
        return None
    return 1e3 * max(per) / record["intervals"]
