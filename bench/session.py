"""Building the system under test from a cell, with the benchmark's own
weights, and the pieces every driver shares: the window's compile
counter, the trace around it, and the process clock."""
from __future__ import annotations

import contextlib
import os
import shutil
import threading
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from bench import spans as spans_mod
from bench import trace as trace_mod
from bench.reference import keys as ref_keys
from bench.reference import nets


def process_start() -> float:
    """Wall-clock time at which this process started (``time.time()``
    scale), from /proc; the set-up time counts from here."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f
                         if l.startswith("btime"))
        return btime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


@contextlib.contextmanager
def configured(cell):
    """Run the program at the matmul precision its configuration states
    (``matmul_precision``): JAX's default on a TPU is one bfloat16 pass,
    which is not the float32 the configuration names. Set process-wide,
    since the runtimes trace programs on their own threads; restored on
    exit."""
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", before)


def ref_policy(spec: dict) -> dict:
    """The reference's view of the spec's policy: its name and every
    size, stated in the configuration file."""
    return {"name": spec["policy"]["name"], "arch": spec["policy"]["kwargs"]}


def ref_hp(spec: dict) -> dict:
    """Every hyperparameter the reference needs, read from the spec
    (the configuration states each one, so no default of the system
    under test is assumed)."""
    hts, opt = spec["hts"], spec["optimizer"]["kwargs"]
    hp = {k: hts[k] for k in ("alpha", "seed", "gamma", "value_coef",
                              "entropy_coef", "use_gae", "gae_lambda",
                              "ppo_clip")}
    hp.update(algorithm=spec["algorithm"], lr=opt["lr"], eps=opt["eps"],
              rho=opt["decay"])
    return hp


def make_weights(spec: dict, obs_shape, n_actions, seed: int,
                 bench_dir: Path):
    """The policy's weights from ``seed``, made on the device in one
    jitted call, float32 as the configuration states."""
    init, _ = nets.make(ref_policy(spec), obs_shape, n_actions, bench_dir)
    return jax.jit(init)(ref_keys.seed_key(seed))


def build(spec_dict: dict, params, runtime_kwargs: Optional[dict] = None):
    """``repro.api`` Session for a cell's spec, training the
    benchmark's ``params`` (built by the engine's registry, as
    ``api.build`` does, with the benchmark's weights in place of the
    spec's own initialisation)."""
    from repro import api
    from repro.core import engine
    spec = api.from_dict(spec_dict)
    base = api.build(spec)
    kwargs = dict(runtime_kwargs or {})
    name = spec.runtime.name
    if name in ("host", "mesh", "sharded"):
        kwargs.setdefault("batch", spec.batch)
    runtime = engine.make_runtime(name, base.env, base.policy.apply, params,
                                  base.opt, base.cfg, **kwargs)
    return api.Session(spec, runtime, base.env, base.policy, params,
                       base.opt, base.cfg)


class CompileCounter:
    """Counts programs compiled, or loaded from the persistent cache,
    while ``counting`` is set."""

    EVENTS = ("backend_compile", "cache_retrieval")

    def __init__(self):
        self.count = 0
        self.counting = False
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.counting and any(e in event for e in self.EVENTS):
            with self._lock:
                self.count += 1


@contextlib.contextmanager
def window(trace_dir: Optional[Path], counter: CompileCounter):
    """The measured window: compiles counted, and with ``trace_dir`` a
    profiler trace with the ``bench.window`` span around it. Yields a
    dict that holds, once the block has ended, the trace's reduction
    (``bench.trace.reduce``) and the program's spans and scopes in it
    (``bench.spans.reduce``), both read after the window has closed."""
    out = {}
    counter.count, counter.counting = 0, True
    if trace_dir is None:
        try:
            yield out
        finally:
            counter.counting = False
        return
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    try:
        with jax.profiler.trace(str(trace_dir), profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                yield out
    finally:
        counter.counting = False
    planes = trace_mod.load(trace_mod.find_xplane(str(trace_dir)))
    out["trace"] = trace_mod.reduce(planes)
    out["trace"].update(spans_mod.reduce(planes))
    shutil.rmtree(trace_dir, ignore_errors=True)


def host(tree):
    return jax.tree.map(lambda a: np.asarray(jax.device_get(a)), tree)
