"""The program's trace names, and the one helper that opens a host span.

Two kinds of name, each read by the benchmark's per-layer metrics:

* host spans — ``span(name)`` is ``jax.profiler.TraceAnnotation``, a
  TraceMe on the same clock as the device planes of a profiler trace,
  and close to free when no profiler is running. A device idle gap is
  named by the innermost span around it.
* device scopes — ``jax.named_scope`` names inside the compiled
  programs. They change only op metadata (the profiler's ``tf_op``
  path of every op), never a computed value.

``Phases`` accumulates the host runtime's per-phase totals
(``HostConfig(profile=True)``): seconds of a timed phase and counts of
dispatches, added from the same region the phase's span covers.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict

import jax

# ---- host spans: the fit loop (core/trainer.py), on the caller's thread
FIT_SEGMENT = "fit.segment"      # runtime.run_from
FIT_CAPTURE = "fit.capture"      # runtime.state()
FIT_STREAM = "fit.stream"        # on_interval and ReturnStream.extend
FIT_SAVE = "fit.save"            # checkpoint write and prune
FIT_RESUME = "fit.resume"        # restore of the newest checkpoint
FIT_RECOVER = "fit.recover"      # supervisor restore after a failure
# ---- host spans: the threaded host runtime (core/host_runtime.py)
HOST_LEARNER_DRAIN = "host.learner_drain"        # coordinator
HOST_INTERVAL_BARRIER = "host.interval_barrier"  # coordinator
HOST_GRAD_DISPATCH = "host.grad_dispatch"        # coordinator
HOST_APPLY_DISPATCH = "host.apply_dispatch"      # coordinator
HOST_ACTOR_DISPATCH = "host.actor_dispatch"      # actor threads, profile on
HOST_STEP_DISPATCH = "host.step_dispatch"        # stepper, profile on

SPANS = (FIT_SEGMENT, FIT_CAPTURE, FIT_STREAM, FIT_SAVE, FIT_RESUME,
         FIT_RECOVER, HOST_LEARNER_DRAIN, HOST_INTERVAL_BARRIER,
         HOST_GRAD_DISPATCH, HOST_APPLY_DISPATCH, HOST_ACTOR_DISPATCH,
         HOST_STEP_DISPATCH)

# ---- device scopes (core/mesh_runtime.py, core/rollout.py, envs/device)
ROLLOUT = "hts.rollout"          # the interval's rollout half
ACTOR_FORWARD = "actor_forward"  # policy forward and sampling
ENV_STEP = "env_step"            # the batched env step
RENDER = "render"                # a device env's frame rendering
LEARNER = "hts.learner"          # the interval's learner half
PER_ENV_GRAD = "per_env_grad"    # vmap of the width-1 gradient
GRAD_REDUCE = "grad_reduce"      # pairwise tree sums, all-gather
OPTIMIZER = "optimizer"          # delayed_grad.update
DRAIN = "hts.drain"              # the reporting-only trailing pass

# inside grad_reduce, the path the per-env tree sum took (core/batch.py)
TREE_HALVES = "tree_halves"      # contiguous halves, power-of-two count
TREE_STRIDED = "tree_strided"    # strided pairs, any other count

SCOPES = (ROLLOUT, ACTOR_FORWARD, ENV_STEP, RENDER, LEARNER, PER_ENV_GRAD,
          GRAD_REDUCE, TREE_HALVES, TREE_STRIDED, OPTIMIZER, DRAIN)


def span(name: str):
    """A host span named ``name`` around a ``with`` block."""
    return jax.profiler.TraceAnnotation(name)


_OFF = contextlib.nullcontext()


class _Timed:
    __slots__ = ("phases", "key", "t0")

    def __init__(self, phases: "Phases", key: str):
        self.phases, self.key = phases, key

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.phases.add(self.key, time.perf_counter() - self.t0)


class Phases:
    """Per-phase totals shared by a runtime's threads. With ``on``
    unset every method is a boolean check and ``totals`` stays
    empty."""

    def __init__(self, on: bool):
        self.on = bool(on)
        self.totals: Dict[str, float] = {}
        self._lock = threading.Lock()

    def clear(self) -> None:
        self.totals = {}

    def add(self, key: str, value: float) -> None:
        with self._lock:
            self.totals[key] = self.totals.get(key, 0) + value

    def count(self, key: str) -> None:
        """One more under ``key``."""
        if self.on:
            self.add(key, 1)

    def timed(self, key: str):
        """Context: adds the seconds its block takes to ``key``."""
        return _Timed(self, key) if self.on else _OFF

    def get(self, key: str, q):
        """``q.get()``, with the wait added to ``key``: for waits too
        frequent to pay for a ``with`` (hundreds per interval), so with
        ``on`` unset it costs a call and a boolean check."""
        if not self.on:
            return q.get()
        t0 = time.perf_counter()
        try:
            return q.get()
        finally:
            self.add(key, time.perf_counter() - t0)

    def span(self, name: str):
        """Context: the host span ``name``, opened only when on (for
        phases too frequent to trace always)."""
        return span(name) if self.on else _OFF
