"""The LLM-scale learner as an engine runtime: the TokenStream workload
(repro.data.pipeline) driven through the Runtime contract
(core/engine.py) instead of a bespoke launcher loop.

One "interval" = one delayed-gradient update over one (B, S) token
batch — the exact computation ``repro.launch.train`` has always run
(same ``learner.make_train_step``, same pjit shardings from
repro.sharding.rules, same stream batch order), so porting the launcher
onto this runtime changes its losses by ZERO bits. What the contract
adds on top of the loop:

  * ``run(n)`` is a reset-and-replay; ``state()``/``run_from`` give the
    continuation capsule, so ``run(a + b)`` equals ``run(a)`` +
    ``run_from(state, b)`` bit-exactly (the TokenStream is a pure
    function of (seed, step) — fast-forward IS resume);
  * ``RunResult.metrics`` streams per-interval loss stats, which the
    Session observer hook (repro.api) forwards — the launcher's
    progress printing is an observer now, not loop plumbing.

Stream-batch numbering, pinned for compatibility: batch 0 has always
been consumed by the launcher's shape probe, so interval j trains on
batch j + 1. This runtime reproduces that (the probe batch seeds the
pjit shapes), keeping new runs step-for-step loss-identical with every
run the old launcher loop ever logged or checkpointed.

This runtime is NOT in the engine name registry: every registered
factory takes a single unvectorized Env, while this one consumes a
TokenStream factory. ``repro.api.build`` constructs it for
``runtime="stream"`` specs; the workload/model pair comes from the env
("token_stream") and policy ("backbone") registries.
"""
from __future__ import annotations

import time
from typing import Callable, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import delayed_grad, learner
from repro.core.engine import HTSConfig, RunResult, TrainState
from repro.optim import Optimizer
from repro.sharding import rules

# algorithms whose loss the token-trajectory learner implements
# (stale-correction algorithms need behavior-lagged rollouts, which a
# TokenStream does not produce)
_ALGORITHMS = ("a2c", "ppo")


class StreamRuntime:
    name = "stream"

    def __init__(self, stream_factory: Callable, params, opt: Optimizer,
                 cfg: HTSConfig, model_config,
                 mesh: Union[str, object, None] = "host",
                 n_microbatches: int = 1, batch=None):
        if cfg.algorithm not in _ALGORITHMS:
            raise ValueError(
                f"the stream runtime implements {list(_ALGORITHMS)}, got "
                f"algorithm {cfg.algorithm!r} (stale-correction "
                f"algorithms need behavior-lagged rollouts)")
        if cfg.staleness != 1:
            raise ValueError(
                f"the stream runtime is the delay-1 LLM learner; got "
                f"staleness={cfg.staleness}")
        self.stream_factory = stream_factory
        self.params0 = params
        self.opt = opt
        self.cfg = cfg
        self.model_config = model_config
        self.mesh = self._resolve_mesh(mesh)
        # typed geometry (repro.core.batch): grad_accumulation maps to
        # the learner's microbatch count; replica scale-out belongs to
        # the sharded runtimes, so n_replicas must be unset/1 here —
        # make_train_step validates both
        self.batch = batch
        self.n_microbatches = n_microbatches
        self._built = False
        self.dg = None
        self.stream = None
        self.j = 0
        # reporting-only live observer (repro.api.Session installs it):
        # called as ``on_interval(j, {"loss": ..., ...})`` per update
        self.on_interval: Optional[Callable[[int, dict], None]] = None

    @staticmethod
    def _resolve_mesh(mesh):
        from repro.launch.mesh import make_host_mesh, make_production_mesh
        if mesh is None or mesh == "host":
            return make_host_mesh()
        if mesh in ("pod", "multipod"):
            return make_production_mesh(multi_pod=(mesh == "multipod"))
        if isinstance(mesh, str):
            raise ValueError(f"unknown mesh name {mesh!r}; known: "
                             f"['host', 'pod', 'multipod'] (or pass a "
                             f"live Mesh via build overrides)")
        return mesh

    # ------------------------------------------------------------ build
    def _build(self) -> None:
        if self._built:
            return
        from repro.launch.mesh import as_shardings
        mesh, opt = self.mesh, self.opt
        step_fn = learner.make_train_step(self.model_config, opt,
                                          self.cfg.algorithm,
                                          self.n_microbatches,
                                          batch_geometry=self.batch)
        dg0 = jax.eval_shape(
            lambda: delayed_grad.init(self.params0, opt))
        # the probe batch: REAL batch 0 off a fresh stream, exactly the
        # shape probe the launcher loop took (and why training starts at
        # batch 1 — see module docstring)
        probe = self.stream_factory().next_batch()
        self._batch_shape = jax.eval_shape(lambda: probe)
        pspecs = rules.param_pspecs(
            jax.eval_shape(lambda: self.params0), mesh)
        dg_specs = rules.dg_state_pspecs(dg0, pspecs, mesh)
        b_specs = rules.batch_specs(self._batch_shape, mesh)
        out_specs = (dg_specs,
                     jax.tree.map(lambda _: P(),
                                  jax.eval_shape(step_fn, dg0, probe)[1]))
        with jax.set_mesh(mesh):
            self._jstep = jax.jit(
                step_fn,
                in_shardings=as_shardings(mesh, (dg_specs, b_specs)),
                out_shardings=as_shardings(mesh, out_specs),
                donate_argnums=(0,))
        self._built = True

    def init(self) -> None:
        self._build()
        # params0 copied: the step donates its dg argument, and replays
        # must not chew through the caller's parameter tree
        self.dg = delayed_grad.init(
            jax.tree.map(jnp.copy, self.params0), self.opt)
        self.stream = self.stream_factory().skip(1)   # past the probe
        self.j = 0

    # ---------------------------------------------------- continuation
    def state(self) -> TrainState:
        if self.dg is None:
            self.init()
        return TrainState(
            algo=jax.tree.map(jnp.copy, self.dg),
            env_state={}, obs={}, buffer={},
            interval=jnp.asarray(self.j, jnp.int32))

    def run(self, n_intervals: int) -> RunResult:
        self.init()
        return self._segment(n_intervals)

    def run_from(self, state: TrainState, n_intervals: int,
                 finalize: bool = True) -> RunResult:
        del finalize   # updates are consumed inline; nothing trails
        self._build()
        self.dg = delayed_grad.DelayedGradState(
            *jax.tree.map(jnp.copy, tuple(state.algo)))
        self.j = int(state.interval)
        self.stream = self.stream_factory().skip(1 + self.j)
        return self._segment(n_intervals)

    # -------------------------------------------------------- the loop
    def _segment(self, n_intervals: int) -> RunResult:
        t0 = time.perf_counter()
        stats_log = []
        for j in range(self.j, self.j + n_intervals):
            batch = self.stream.next_batch()
            self.dg, stats = self._jstep(self.dg, batch)
            stats_log.append(stats)
            if self.on_interval is not None:
                self.on_interval(j, {k: float(v)
                                     for k, v in stats.items()})
        self.j += n_intervals
        metrics = {}
        if stats_log:
            metrics = {k: np.asarray([s[k] for s in stats_log],
                                     np.float32)
                       for k in stats_log[0]}
        jax.block_until_ready((self.dg.params, metrics))
        wall = time.perf_counter() - t0
        B = self.stream.batch
        S = self.stream.seq
        steps = n_intervals * B * S          # tokens = env steps
        empty = np.zeros((n_intervals, 0, 0), np.float32)
        return RunResult(
            params=self.dg.params, state=self.dg, steps=steps,
            wall_time=wall, sps=steps / max(wall, 1e-9),
            rewards=empty, dones=empty, metrics=metrics or None)
