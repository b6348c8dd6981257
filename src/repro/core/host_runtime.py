"""Faithful threaded HTS-RL (paper Fig. 1(e) / Fig. 2(d)) on a single host.

Process layout (paper -> here): executor processes -> one persistent
thread per environment replica; actor processes -> ``n_actors``
persistent threads batching whatever observations are in the state
buffer; learner -> the coordinator thread. JAX releases the GIL inside
compiled computations, so threads give the same concurrency the paper
gets from processes (see DESIGN.md §2).

The hot path dispatches O(1) compiled programs per *batch*, not per
env-step:

  * persistent worker pools — actor/executor/stepper threads are spawned
    once per ``run`` segment and reused across all intervals;
  * batched env stepping — executors submit ready (env, step, action)
    requests to a stepper that groups them into ONE fixed-shape padded
    dispatch over device-resident stacked env states;
  * per-interval seed tables — all ``(env, step)`` action and transition
    keys for an interval are derived in one device call;
  * slab hand-off — the trajectory storage is a ``SlabRing`` of K+1
    preallocated numpy slabs passed to the learner by reference.

The staleness-K pipeline (``HTSConfig.staleness``; DESIGN.md §4): the
learner is split into a *gradient* pass and an *apply* pass. The
gradient for interval ``j``'s data is dispatched the moment interval
``j`` finishes — at theta_j, the params that generated it — and applied
K intervals later (delay-K update, Eq. 6 generalized):

    theta_{j+1} = theta_j + eta * grad J(theta_{j-K}, D^{theta_{j-K}})

so every gradient has K intervals of rollout wall time to complete
before anything blocks on it. At K=1 this is exactly the paper's
double-buffer schedule (the coordinator effectively blocks on the
previous interval's learner); at K>1 the coordinator only blocks on the
learner pass from K+1 intervals back, which is what recovers
asynchronous-style throughput under a slow learner while keeping the
staleness bound — and the determinism contract — intact
(benchmarks/staleness_sweep.py measures the frontier).

Key properties implemented exactly as in the paper:
  * state buffer / action buffer between executors and actors (queues),
    actors poll and batch asynchronously;
  * per-observation executor-attached seeds -> deterministic actions
    regardless of actor count/batching (Sec. 4.1 'full determinism');
  * K+1 data storages with the ring barrier (core/buffers.SlabRing: the
    coordinator blocks on the gradient pass that read a slab before the
    slab is reused);
  * batch synchronization every alpha steps.

The actor computation and the learner update are the SAME functions the
fused/sharded runtimes use (core/rollout.actor_forward,
mesh_runtime.make_learner_update and its grad/apply split) — the thread
scheduling here and the XLA scheduling there are two executions of one
program, which is why tests/test_equivalence.py and tests/
test_staleness.py can demand bit-identical parameters at every K. Batch
composition cannot affect values: keys are pure functions of
(seed, env_id, step) and both the actor forward and the batched env
step are vmapped row-independent programs, so ANY grouping of ready
envs — including the out-of-order groupings ``step_time`` skew produces
— writes bit-identical trajectories (tests/test_perf_guards.py).

``step_time`` (optional) injects simulated environment step durations via
``time.sleep``; ``learner_time`` injects a simulated per-update learner
duration (a dedicated sim thread completes gradient passes FIFO, one
``learner_time`` apart — a serial learner) for wall-clock throughput
experiments. Neither changes a single computed value.
"""
from __future__ import annotations

import queue
import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import delayed_grad, determinism, spans
from repro.core.buffers import SlabRing
from repro.core.engine import (HTSConfig, RunResult, TrainState,
                               register_runtime)
from repro.core.mesh_runtime import (make_grad_fn, make_learner_update,
                                     make_ring_drain)
from repro.core.rollout import actor_forward
from repro.envs.interfaces import Env
from repro.envs.steptime import StepTimeModel
from repro.faults import FaultInjector, FaultPlan
from repro.optim import Optimizer

_SHUTDOWN = object()          # queue sentinel for pool teardown


@dataclass
class HostConfig:
    n_actors: int = 4
    step_time: Optional[StepTimeModel] = None
    time_scale: float = 1.0          # multiply simulated durations
    actor_compute: float = 0.0       # optional simulated actor latency
    # simulated per-update learner duration: a float (constant) or a
    # StepTimeModel sampled per update index — deterministic like
    # step_time, so throughput experiments are replayable
    learner_time: "float | StepTimeModel" = 0.0
    # accumulate per-phase waits and dispatch counts into
    # ``runtime.profile``, and trace the actor and stepper dispatches
    profile: bool = False


@register_runtime("host")
class HostHTSRL:
    name = "host"

    def __init__(self, env: Env, policy_apply: Callable, params,
                 opt: Optimizer, cfg: HTSConfig,
                 host: Optional[HostConfig] = None,
                 faults: "Optional[FaultInjector | FaultPlan]" = None,
                 batch=None, **host_kwargs):
        if host is not None and host_kwargs:
            # both forms at once used to silently discard the kwargs —
            # e.g. HostHTSRL(..., host=HostConfig(), n_actors=8) ran
            # with 4 actors and nobody noticed
            raise TypeError(
                f"pass either host=HostConfig(...) or HostConfig field "
                f"kwargs, not both (got host and {sorted(host_kwargs)})")
        if cfg.staleness < 1:
            raise ValueError(f"staleness must be >= 1, got {cfg.staleness}")
        self.env = env
        # the batched env the stepper dispatches: vmapped scalar env
        # ("host", today's semantics) or the natively-batched device
        # port ("device" — same thread/dispatch cadence, scatter-free
        # batched programs; the fused runtimes move this whole loop
        # on-device). Bit-identical either way (DESIGN.md §2.2);
        # resolved HERE so bad backends/envs fail at construction.
        from repro.envs.device import batched_env
        self.venv = batched_env(env, cfg.n_envs, cfg.env_backend)
        self.cfg = cfg
        self.host = host if host is not None else HostConfig(**host_kwargs)
        # batch geometry (repro.core.batch): the host runtime has one
        # replica, so any configured (grad_accumulation, n_replicas)
        # factorization is reproduced as chunks = A*R sequential
        # microbatch blocks inside the gradient pass — bit-exact to the
        # physically-replicated run by the canonical-reduction contract
        # (DESIGN.md §12). micro_batch is thus the gradient block size;
        # the slab ring stays (alpha, n_envs) — actors fill the global
        # slab, the learner scans it in micro_batch-sized blocks.
        from repro.core.batch import BatchConfig
        self.batch = BatchConfig.of(batch)
        self.geometry = self.batch.resolve(cfg.n_envs, default_replicas=1)
        self.opt = opt
        self.policy_apply = policy_apply
        self.params0 = params
        # deterministic chaos (DESIGN.md §11): worker loops and the
        # coordinator poll this injector at their logical (site,
        # interval) points. An injected exc rides the SAME paths a real
        # failure does — _guard capture for workers, coordinator raise
        # for the learner — so the chaos tests exercise the production
        # failure machinery, not a parallel one. None (default): zero
        # hot-path cost beyond one attribute check per dispatch.
        if faults is not None and not isinstance(faults, FaultInjector):
            faults = FaultInjector(FaultPlan.of(faults))
        self._faults = faults
        self._built = False
        self.dg = None    # built lazily: run() always starts via init()
        self._phases = spans.Phases(self.host.profile)
        # reporting-only live observer: called by the coordinator as
        # ``on_interval(j, {"rewards": (alpha, n_envs), "dones": ...})``
        # the moment interval j's slab is complete (repro.api.Session
        # installs it). Never touches the training computation.
        self.on_interval: Optional[Callable[[int, dict], None]] = None

    # ------------------------------------------------------------- build
    def _build(self) -> None:
        """Compile-once pieces (jitted fns, slab specs); reused across
        init() resets so warm reruns don't recompile or reallocate."""
        if self._built:
            return
        cfg, env, policy_apply = self.cfg, self.env, self.policy_apply
        master = jax.random.key(cfg.seed)

        venv = self.venv            # resolved at construction (__init__)
        self._env_reset_v = jax.jit(venv.reset)

        # all (env, step) action/transition keys for interval j in ONE
        # device call — the executor hot loop never touches the PRNG
        def make_tables(j):
            gsteps = j * cfg.alpha + jnp.arange(cfg.alpha, dtype=jnp.int32)
            ids = jnp.arange(cfg.n_envs, dtype=jnp.int32)

            def key_data(e, g):
                return jax.random.key_data(determinism.obs_key(master, e, g))

            def per_step(g):
                return (jax.vmap(lambda e: key_data(e, g))(ids),
                        jax.vmap(lambda e: key_data(e + 1_000_003, g))(ids))

            return jax.vmap(per_step)(gsteps)   # 2 x (alpha, n_envs, key)

        self._tables_fn = jax.jit(make_tables)

        # fixed-batch actor forward (padded to n_envs -> one compile);
        # shares core/rollout.actor_forward with the fused runtimes.
        # Keys are gathered from the interval table by (step, env) — the
        # batch composition actors happen to see cannot change them.
        def actor_fwd(p, obs, ids, ts, table):
            keys = jax.vmap(jax.random.wrap_key_data)(table[ts, ids])
            return actor_forward(policy_apply, p, obs, keys)

        self._actor_fwd = jax.jit(actor_fwd)

        # fixed-batch env stepping over device-resident stacked states:
        # gather the ready rows, vmap one step, scatter back in place
        # (donated -> XLA updates the state buffer without reallocating).
        # Padding repeats the last request; duplicate scatter indices
        # then write identical values, so the result is deterministic.
        def step_batch(env_states, actions, ids, ts, table):
            keys = jax.vmap(jax.random.wrap_key_data)(table[ts, ids])
            sel = jax.tree.map(lambda x: x[ids], env_states)
            with jax.named_scope(spans.ENV_STEP):
                ns, nobs, r, d = venv.step(sel, actions, keys)
            env_states = jax.tree.map(
                lambda full, rows: full.at[ids].set(rows), env_states, ns)
            return env_states, nobs, r, d

        self._step_batch = jax.jit(step_batch, donate_argnums=(0,))

        # the learner, split at the staleness pipeline's joint:
        #   grad   — dispatched the moment interval j's data is complete,
        #            at theta_j (the params that generated it). Depends
        #            only on (theta_j, D_j), so it runs concurrently with
        #            the next K intervals of rollout.
        #   apply  — consumes the K-intervals-old pending gradient and
        #            advances (params, behavior history, opt state).
        # The fused runtimes compute the identical composition inside one
        # XLA program; splitting changes scheduling, not values.
        self._grad_fn = jax.jit(make_grad_fn(
            policy_apply, cfg, grad_accumulation=self.geometry.chunks))

        def stream_apply(params_prev, opt_state, step, params, grads):
            dg = delayed_grad.DelayedGradState(params, params_prev,
                                               opt_state, step)
            with jax.named_scope(spans.OPTIMIZER):
                return delayed_grad.update(dg, grads, self.opt)

        # theta_{j-K} (the history's oldest slot) and the old opt state
        # are dead once the update is applied, so they are donated and
        # updated in place. params (theta_j) is NOT donated — the actor
        # pool is still sampling with it, and in-flight gradient passes
        # read the unstacked theta buffers it chains from.
        self._apply_fn = jax.jit(stream_apply, donate_argnums=(0, 1))

        # trailing reporting-only drain of the K pending ring slots: the
        # SAME drain the fused runtimes jit (make_ring_drain), must NOT
        # donate (self.dg and the capsule keep using its inputs)
        learn = make_learner_update(
            policy_apply, self.opt, cfg,
            grad_accumulation=self.geometry.chunks)
        self._final_fn = make_ring_drain(learn, cfg.staleness)

        obs_shape = env.obs_shape
        self._spec = {
            "obs": (obs_shape, np.float32 if obs_shape else np.int32),
            "actions": ((), np.int32),
            "rewards": ((), np.float32),
            "dones": ((), np.float32),
            "behavior_logprob": ((), np.float32),
        }
        self._slabs = SlabRing(cfg.alpha, cfg.n_envs, self._spec,
                               n_slots=cfg.staleness + 1)
        self._built = True

    def init(self) -> None:
        cfg = self.cfg
        self._build()
        # params0 is copied so in-place (donating) updates can never
        # invalidate the caller's parameter tree across run() replays
        self.dg = delayed_grad.init(jax.tree.map(jnp.copy, self.params0),
                                    self.opt, staleness=cfg.staleness)
        keys = jax.random.split(jax.random.key(cfg.seed ^ 0x5EED),
                                cfg.n_envs)
        self.env_states, obs = self._env_reset_v(keys)
        self.obs_np = np.array(obs)     # writable host copy
        self.j = 0              # global interval counter
        # gradient passes in flight: oldest-first, one entry per
        # unconsumed ring slot — {"j", "traj" (slab-aliased), "grads"
        # (dispatched), "ready" (sim-learner gate or None)}
        self._pending: deque = deque()
        self._reset_logs()

    def _reset_logs(self) -> None:
        self.rewards_log: list = []
        self.dones_log: list = []
        self.sps_steps = 0
        self.wall_time = 0.0
        self._phases.clear()

    @property
    def profile(self) -> Dict[str, float]:
        """Per-segment totals with ``HostConfig(profile=True)``, empty
        without: seconds the executors waited for actions
        (``actor_wait``) and env steps (``env_step_wait``) summed over
        threads, seconds the coordinator waited on the learner
        (``learner_drain``), and the actor and stepper dispatch counts
        (``actor_dispatches``, ``step_dispatches``)."""
        return self._phases.totals

    # ------------------------------------------------------ continuation
    def _zero_traj(self):
        """An empty ring slot: all-zero trajectory with dones=1 (mirrors
        mesh_runtime.init_carry so host/mesh capsules are one structure)."""
        cfg = self.cfg
        obs_shape, obs_dtype = self._spec["obs"]
        return {
            "obs": jnp.zeros((cfg.alpha, cfg.n_envs) + tuple(obs_shape),
                             obs_dtype),
            "actions": jnp.zeros((cfg.alpha, cfg.n_envs), jnp.int32),
            "rewards": jnp.zeros((cfg.alpha, cfg.n_envs), jnp.float32),
            "dones": jnp.ones((cfg.alpha, cfg.n_envs), jnp.float32),
            "behavior_logprob": jnp.zeros((cfg.alpha, cfg.n_envs),
                                          jnp.float32),
            "bootstrap_obs": jnp.zeros((cfg.n_envs,) + tuple(obs_shape),
                                       obs_dtype),
        }

    def _buffer_ring(self):
        """The unconsumed read storage as the capsule/drain pytree: slot
        p holds interval ``j - K + p``'s trajectory (zero for intervals
        that never ran). K=1 keeps the plain single-trajectory dict so
        the capsule structure is unchanged from the double-buffer days;
        K>1 stacks the K slots oldest-first (mirrors the fused carry)."""
        K = self.cfg.staleness
        have = {e["j"]: e["traj"] for e in self._pending}
        slots = [have.get(self.j - K + p) or self._zero_traj()
                 for p in range(K)]
        if K == 1:
            return dict(slots[0])
        return jax.tree.map(lambda *xs: jnp.stack(xs), *slots)

    def state(self) -> TrainState:
        """The continuation capsule — structurally identical to the fused
        runtimes' (same TrainState fields, same buffer pytree), so a host
        checkpoint restores into a mesh/sharded run and vice versa. Every
        leaf is COPIED: the runtime's own buffers are donated/slab-backed
        and a later segment would otherwise mutate them under the capsule."""
        if self.dg is None:
            self.init()
        capsule = TrainState(self.dg, self.env_states,
                             jnp.asarray(self.obs_np), self._buffer_ring(),
                             jnp.asarray(self.j, jnp.int32))
        return jax.tree.map(jnp.copy, capsule)

    def _restore(self, state: TrainState) -> None:
        # copies decouple the capsule from this runtime's donated buffers
        self.dg = delayed_grad.DelayedGradState(
            *jax.tree.map(jnp.copy, tuple(state.algo)))
        self.env_states = jax.tree.map(jnp.copy, state.env_state)
        self.obs_np = np.array(state.obs)
        self.j = int(state.interval)
        K = self.cfg.staleness
        # re-dispatch the in-flight gradient passes the capsule implies:
        # ring slot p (data of interval j-K+p) differentiated at its
        # behavior params (history slot p) — exactly the gradients the
        # uninterrupted run would have pending
        self._pending = deque()
        for p in range(K):
            i = self.j - K + p
            if i < 0:
                continue          # slot never filled (j < K)
            traj = jax.tree.map(
                jnp.copy,
                dict(state.buffer) if K == 1
                else jax.tree.map(lambda x, _p=p: x[_p], dict(state.buffer)))
            bp = (self.dg.params_prev if K == 1 else
                  jax.tree.map(lambda h, _p=p: h[_p], self.dg.params_prev))
            self._pending.append({"j": i, "traj": traj,
                                  "grads": self._grad_fn(bp, traj),
                                  "ready": None})
        self._reset_logs()

    def run_from(self, state: TrainState, n_intervals: int,
                 finalize: bool = True) -> RunResult:
        self._build()
        self._restore(state)
        return self._segment(n_intervals, finalize)

    # ------------------------------------------------------------- pools
    def _spawn_pools(self) -> None:
        cfg = self.cfg
        # a worker that survived a previous segment's teardown (stuck in
        # a long dispatch/sleep past the join timeout) must never deliver
        # a stale result into THIS segment's fresh slot queues — that
        # would silently corrupt the trajectory. Refuse loudly instead.
        zombies = [th for th in getattr(self, "_zombies", ())
                   if th.is_alive()]
        if zombies:
            raise RuntimeError(
                f"{len(zombies)} worker thread(s) from a previous segment "
                f"are still running after teardown; refusing to start a "
                f"new segment on this runtime")
        self._state_q: "queue.Queue" = queue.Queue()
        self._step_q: "queue.Queue" = queue.Queue()
        self._sim_q: "queue.Queue" = queue.Queue()
        self._action_slots = [queue.Queue() for _ in range(cfg.n_envs)]
        self._step_slots = [queue.Queue() for _ in range(cfg.n_envs)]
        self._start_barrier = threading.Barrier(cfg.n_envs + 1)
        self._end_barrier = threading.Barrier(cfg.n_envs + 1)
        self._pool_stop = False
        self._pool_exc: list = []
        self._threads = (
            [threading.Thread(target=self._guard, args=(self._actor_loop,),
                              daemon=True)
             for _ in range(self.host.n_actors)]
            + [threading.Thread(target=self._guard, args=(self._stepper_loop,),
                                daemon=True)]
            + [threading.Thread(target=self._guard,
                                args=(self._executor_loop, i), daemon=True)
               for i in range(cfg.n_envs)])
        self._sim_learner_on = (
            isinstance(self.host.learner_time, StepTimeModel)
            or bool(self.host.learner_time))
        if self._sim_learner_on:
            self._threads.append(threading.Thread(
                target=self._guard, args=(self._sim_learner_loop,),
                daemon=True))
        for th in self._threads:
            th.start()

    def _release_pool_waits(self) -> None:
        """Unblock EVERY wait a pool thread can be parked on: both
        barriers, the shared request queues, and the per-env slot
        queues. Idempotent; used by normal teardown and by _guard when a
        worker dies (an executor blocked on its slot would otherwise
        never see a sentinel and leak)."""
        self._pool_stop = True
        for barrier in (self._start_barrier, self._end_barrier):
            try:
                barrier.abort()
            except Exception:
                pass
        for _ in range(self.host.n_actors):
            self._state_q.put(_SHUTDOWN)
        self._step_q.put(_SHUTDOWN)
        self._sim_q.put(_SHUTDOWN)
        for slot in list(self._action_slots) + list(self._step_slots):
            slot.put(_SHUTDOWN)
        # the coordinator may be parked on a pending gradient's ready
        # gate (sim learner): if the sim thread is the one that died, no
        # one would ever set it — wake every pending gate so the
        # coordinator reaches a (broken) barrier and re-raises via
        # _check_pool instead of hanging
        for ent in list(getattr(self, "_pending", ())):
            if ent.get("ready") is not None:
                ent["ready"].set()

    def _shutdown_pools(self) -> None:
        self._release_pool_waits()
        for th in self._threads:
            th.join(timeout=10.0)
        # keep handles to any straggler so _spawn_pools can refuse to
        # run a new segment while it is still alive
        self._zombies = [th for th in self._threads if th.is_alive()]
        self._threads = []

    def _guard(self, fn, *args) -> None:
        """Worker wrapper: record the exception (with its traceback, for
        the coordinator to re-raise loudly) and release every pool wait
        so the coordinator (and sibling workers) unblock instead of
        hanging. Catches BaseException: a KeyboardInterrupt/SystemExit
        delivered to a worker thread must ALSO fail the run — an
        uncaught one would kill the thread silently and leave the
        coordinator blocked on a barrier forever."""
        try:
            fn(*args)
        except BaseException as e:      # noqa: BLE001 — repropagated
            if self._pool_stop:
                return                  # normal teardown (aborted barrier)
            self._pool_exc.append((e, traceback.format_exc()))
            self._release_pool_waits()

    def _check_pool(self) -> None:
        if self._pool_exc:
            exc, tb = self._pool_exc[0]
            raise RuntimeError(
                f"host runtime worker thread died: {exc!r}\n"
                f"--- worker thread traceback ---\n{tb}") from exc

    def _drain_batch(self, q: "queue.Queue", first) -> Optional[list]:
        """The shared actor/stepper batching protocol: take the blocking
        ``first`` item, greedily drain up to ``n_envs`` ready requests,
        and re-surface a shutdown sentinel for sibling workers. Returns
        None on shutdown."""
        if first is _SHUTDOWN:
            return None
        batch = [first]
        while len(batch) < self.cfg.n_envs:
            try:
                item = q.get_nowait()
            except queue.Empty:
                break
            if item is _SHUTDOWN:
                q.put(_SHUTDOWN)      # keep sentinel for sibling workers
                break
            batch.append(item)
        return batch

    @staticmethod
    def _pad(n: int, *cols):
        """Pad int32 request columns to the fixed dispatch width ``n`` by
        repeating the last request (identical padded rows compute —
        and, for scatters, write — identical values)."""
        out = []
        for col in cols:
            a = np.asarray(col, np.int32)
            pad = n - a.shape[0]
            out.append(np.concatenate([a, np.repeat(a[-1:], pad)])
                       if pad else a)
        return out

    # ------------------------------------------------------------ actors
    def _actor_loop(self) -> None:
        n = self.cfg.n_envs
        q = self._state_q
        prof = self._phases
        while True:
            batch = self._drain_batch(q, q.get())
            if batch is None:
                return
            if self._faults is not None:
                self._faults.fire("actor", self._cur_j)
            k = len(batch)
            ids, ts = self._pad(n, [b[0] for b in batch],
                                [b[1] for b in batch])
            obs = np.stack([b[2] for b in batch])
            if k < n:
                obs = np.concatenate([obs, np.repeat(obs[-1:], n - k, 0)])
            if self.host.actor_compute:
                time.sleep(self.host.actor_compute * self.host.time_scale)
            with prof.span(spans.HOST_ACTOR_DISPATCH):
                actions, blp = self._actor_fwd(self._behavior, obs, ids, ts,
                                               self._actor_table)
                actions = np.asarray(actions)
                blp = np.asarray(blp)
            prof.count("actor_dispatches")
            for i in range(k):
                self._action_slots[ids[i]].put(
                    (int(actions[i]), float(blp[i])))

    # ----------------------------------------------------------- stepper
    def _stepper_loop(self) -> None:
        """Groups ready (env, step, action) requests into one padded
        fixed-shape dispatch. Which envs land in which group is racy and
        irrelevant: each row's transition depends only on its own
        (state, action, key)."""
        n = self.cfg.n_envs
        q = self._step_q
        prof = self._phases
        while True:
            batch = self._drain_batch(q, q.get())
            if batch is None:
                return
            if self._faults is not None:
                self._faults.fire("stepper", self._cur_j)
            k = len(batch)
            ids, ts, acts = self._pad(n, [b[0] for b in batch],
                                      [b[1] for b in batch],
                                      [b[2] for b in batch])
            if self._faults is not None:
                # distinct from "stepper" death: this models the ENV
                # raising mid-step (the exception surfaces from the env
                # dispatch point, inside the stepper thread)
                self._faults.fire("env_step", self._cur_j)
            with prof.span(spans.HOST_STEP_DISPATCH):
                self.env_states, nobs, r, d = self._step_batch(
                    self.env_states, acts, ids, ts, self._step_table)
                nobs = np.asarray(nobs)
                r = np.asarray(r)
                d = np.asarray(d)
            prof.count("step_dispatches")
            for i in range(k):
                self._step_slots[ids[i]].put(
                    (nobs[i], float(r[i]), float(d[i])))

    # ------------------------------------------------------- sim learner
    def _sim_learner_loop(self) -> None:
        """The simulated serial learner (``HostConfig.learner_time``):
        completes submitted gradient passes FIFO, each taking the real
        compute time plus the simulated duration — so gradient i's
        completion chains on gradient i-1's, like a single learner
        process. Durations come from a constant or a seeded
        StepTimeModel keyed on the data interval index (deterministic,
        replayable). Only the *timing* of the ready gate is simulated;
        the gradient values were dispatched by the coordinator
        untouched."""
        lt = self.host.learner_time
        while True:
            item = self._sim_q.get()
            if item is _SHUTDOWN:
                return
            data_j, grads, ready = item
            jax.block_until_ready(grads)
            dt = (lt.sample(0, data_j, self.cfg.seed ^ 0x1EA12)
                  if isinstance(lt, StepTimeModel) else lt)
            time.sleep(dt * self.host.time_scale)
            ready.set()

    # --------------------------------------------------------- executors
    def _executor_loop(self, env_id: int) -> None:
        cfg, host = self.cfg, self.host
        prof = self._phases
        while True:
            try:
                self._start_barrier.wait()
            except threading.BrokenBarrierError:
                return                  # pool teardown
            if self._pool_stop:
                return
            j = self._cur_j
            if self._faults is not None:
                self._faults.fire("executor", j)
            slab, boot = self._cur_slab, self._cur_boot
            obs = self.obs_np[env_id]
            for t in range(cfg.alpha):
                self._state_q.put((env_id, t, obs))
                got = prof.get("actor_wait", self._action_slots[env_id])
                if got is _SHUTDOWN:
                    return              # a sibling worker died mid-interval
                action, blp = got
                if host.step_time is not None:
                    dt = host.step_time.sample(env_id, j * cfg.alpha + t,
                                               cfg.seed)
                    time.sleep(dt * host.time_scale)
                self._step_q.put((env_id, t, action))
                got = prof.get("env_step_wait", self._step_slots[env_id])
                if got is _SHUTDOWN:
                    return
                nobs, r, d = got
                slab["obs"][t, env_id] = obs
                slab["actions"][t, env_id] = action
                slab["rewards"][t, env_id] = r
                slab["dones"][t, env_id] = d
                slab["behavior_logprob"][t, env_id] = blp
                obs = nobs
            self.obs_np[env_id] = obs
            boot[env_id] = obs
            self._end_barrier.wait()

    # --------------------------------------------------------------- run
    def run(self, n_intervals: int) -> RunResult:
        self.init()   # engine contract: every run starts from params0
        return self._segment(n_intervals)

    def _run_intervals(self, n_intervals: int) -> None:
        cfg = self.cfg
        K = cfg.staleness
        prof = self._phases
        self._spawn_pools()
        try:
            for j in range(self.j, self.j + n_intervals):
                self._check_pool()
                # ring-reuse barrier: the slab interval j rewrites was
                # last read by the gradient pass over interval j-K-1's
                # data, which the apply dispatched at interval j-1
                # consumed — blocking on the applied state therefore
                # guarantees "read exhausted" before the roles rotate
                # (DESIGN.md §4). With K > 1 that gradient was dispatched
                # K intervals ago, so a learner slower than one interval
                # no longer stalls every interval.
                with (spans.span(spans.HOST_LEARNER_DRAIN),
                      prof.timed("learner_drain")):
                    jax.block_until_ready(self.dg)
                slab, boot = self._slabs.write_view(j)
                self._cur_j = j
                self._cur_slab, self._cur_boot = slab, boot
                self._behavior = self.dg.params     # theta_j
                self._actor_table, self._step_table = self._tables_fn(
                    jnp.asarray(j, jnp.int32))
                self._start_barrier.wait()          # release executors
                # learner apply runs concurrently with rollout j: consume
                # the K-intervals-old pending gradient (delay-K rule,
                # Eq. 6); the first K intervals have nothing pending yet
                # and skip (the behavior history already holds theta_0)
                if len(self._pending) == K:
                    # peek, wait, THEN pop: the entry must stay visible
                    # to _release_pool_waits while the coordinator is
                    # parked on its ready gate, or a dying sim-learner
                    # thread could strand the coordinator forever
                    ent = self._pending[0]
                    with spans.span(spans.HOST_APPLY_DISPATCH):
                        if ent["ready"] is not None:
                            ent["ready"].wait()
                        self._pending.popleft()
                        self.dg = self._apply_fn(
                            self.dg.params_prev, self.dg.opt_state,
                            self.dg.step, self.dg.params, ent["grads"])
                with spans.span(spans.HOST_INTERVAL_BARRIER):
                    self._end_barrier.wait()        # executors finished
                # interval done: dispatch the gradient for D_j at theta_j
                # immediately (by reference to the slab — only the small
                # reporting streams are copied). It now has K intervals
                # of rollout wall time before its apply blocks on it.
                with spans.span(spans.HOST_GRAD_DISPATCH):
                    traj_j = self._slabs.as_traj(j)
                    grads = self._grad_fn(self._behavior, traj_j)
                if self._faults is not None:
                    # "learner" site, at interval j's gradient dispatch:
                    # exc -> the learner dies here (coordinator raise);
                    # nan -> the dispatched update is all-NaN, poisoning
                    # params at the apply K intervals later — detected
                    # by the supervisor's finite check BEFORE any save
                    # (core/trainer.LearnerDiverged)
                    ev = self._faults.fire("learner", j)
                    if ev is not None:          # kind == "nan"
                        grads = jax.tree.map(
                            lambda g: jnp.full_like(g, jnp.nan), grads)
                ready = None
                if self._sim_learner_on:
                    ready = threading.Event()
                    self._sim_q.put((j, grads, ready))
                self._pending.append({"j": j, "traj": traj_j,
                                      "grads": grads, "ready": ready})
                self.rewards_log.append(slab["rewards"].copy())
                self.dones_log.append(slab["dones"].copy())
                self.sps_steps += cfg.alpha * cfg.n_envs
                if self.on_interval is not None:
                    # the copies above decouple the observer from slab
                    # reuse; rollout j+1 proceeds while it runs
                    self.on_interval(j, {"rewards": self.rewards_log[-1],
                                         "dones": self.dones_log[-1]})
            self.j += n_intervals
        except threading.BrokenBarrierError:
            self._check_pool()
            raise
        finally:
            self._shutdown_pools()
        self._check_pool()

    def _segment(self, n_intervals: int, finalize: bool = True) -> RunResult:
        cfg = self.cfg
        t_start = time.perf_counter()
        if n_intervals > 0:
            self._run_intervals(n_intervals)
        # trailing learner drain of the K pending ring slots — REPORTING
        # ONLY: self.dg stays mid-stream (ring unconsumed), so
        # state()/run_from continue bit-exactly without double-applying
        # these updates (same split as ScanRuntimeBase._finalize).
        dg_final = self.dg
        if finalize:
            dg_final = self._final_fn(self.dg, self._buffer_ring(),
                                      jnp.asarray(self.j, jnp.int32))
        jax.block_until_ready(dg_final)   # honest wall time / SPS
        self.wall_time = time.perf_counter() - t_start
        empty = np.zeros((0, cfg.alpha, cfg.n_envs), np.float32)
        return RunResult(
            params=dg_final.params, state=dg_final, steps=self.sps_steps,
            wall_time=self.wall_time,
            sps=self.sps_steps / max(self.wall_time, 1e-9),
            rewards=np.stack(self.rewards_log) if self.rewards_log else empty,
            dones=np.stack(self.dones_log) if self.dones_log else empty)
