"""Checkpointed continuation driver: wrap any engine runtime with
periodic checkpointing, resume-from-latest, and streaming evaluation.

The engine contract (core/engine.py) makes ``run(n)`` a reset-and-replay;
this module is what turns that into long-lived training that survives
preemption:

    rt = engine.make_runtime("sharded", env, papply, params, opt, cfg)
    trainer = Trainer(rt, checkpoint_dir="ckpts", ckpt_every=50)
    report = trainer.fit(10_000, resume=True)   # picks up where it died

``fit`` drives the runtime exclusively through ``run_from`` in
``ckpt_every``-interval segments, capturing the ``TrainState`` capsule
after each segment and writing it through ``repro.checkpoint.io`` with
versioned metadata (runtime name, algorithm, seed, interval count, and
the streaming-metric carry). Because ``run(a + b)`` equals any partition
into ``run_from`` segments bit-exactly (tests/test_continuation.py), a
checkpointed-and-killed run resumed by a fresh process produces the
EXACT parameters of the uninterrupted run — checkpointing is free of
training-dynamics side effects, on every runtime.

Per-segment reward/done streams feed a ``core.evaluate.ReturnStream``,
whose carry rides inside the checkpoint metadata — so the paper's
evaluation protocol survives preemption too: an episode spanning a
kill/resume boundary is counted once, with the correct return (bit-equal
to the uninterrupted trainer's stream; equal to the one-shot
computation bit-exactly for integer-valued rewards, to ~1 ulp for
arbitrary floats — see ReturnStream).
"""
from __future__ import annotations

import glob
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import io as ckpt_io
from repro.core import evaluate, spans
from repro.core.engine import Runtime, TrainState
from repro.faults import FaultInjector, FaultPlan

CKPT_FORMAT = "hts-trainstate-v1"


def checkpoint_metadata(runtime: Runtime, intervals: int,
                        stream: evaluate.ReturnStream) -> dict:
    """The versioned manifest written beside every trainer-format
    capsule. Module-level so every writer of ``CKPT_FORMAT``
    checkpoints (Trainer segments, TenantPool slice boundaries) emits
    the same manifest and the same ``_resume`` validation applies."""
    cfg = runtime.cfg
    meta = {
        "format": CKPT_FORMAT,
        "runtime": runtime.name,
        "algorithm": cfg.algorithm,
        "seed": cfg.seed,
        "alpha": cfg.alpha,
        "n_envs": cfg.n_envs,
        "staleness": cfg.staleness,
        "intervals": intervals,
        "metrics": stream.state_dict(),
    }
    # batch geometry rides in the MANIFEST, not the capsule (the
    # capsule is a pure-array pytree identical across geometries —
    # that is the point of the determinism contract). Recorded so
    # _resume can validate a restore onto a different factorization
    # loudly instead of guessing.
    geom = getattr(runtime, "geometry", None)
    if geom is not None:
        meta["batch"] = geom.canonical()
    return meta


def prune_checkpoints(checkpoint_dir: str, keep: int) -> None:
    """Retain the ``keep`` most-recent ``step_*`` checkpoints
    (0 = keep all)."""
    if not keep:
        return
    paths = sorted(glob.glob(os.path.join(checkpoint_dir, "step_*.json")))
    for p in paths[:-keep]:
        base = p[:-len(".json")]
        for suffix in (".json", ".npz"):
            try:
                os.remove(base + suffix)
            except OSError:
                pass


class LearnerDiverged(RuntimeError):
    """The segment produced non-finite parameters (a NaN'd/inf'd learner
    step). Raised BEFORE the capsule is checkpointed, so the divergence
    never becomes durable — the supervisor restores the last finite
    capsule and replays. Only checked when a fault plan is configured;
    without one, non-finite params flow through unchanged (pre-existing
    behavior)."""


@dataclass
class TrainReport:
    """What ``Trainer.fit`` returns."""
    params: Any
    state: TrainState            # mid-stream continuation capsule
    intervals: int               # total intervals completed (incl. resumed)
    resumed_from: int            # intervals already done at fit() entry
    steps: int                   # env steps executed by THIS fit call
    wall_time: float
    sps: float
    rewards: np.ndarray          # (intervals_this_fit, alpha, n_envs)
    dones: np.ndarray
    episode_returns: np.ndarray  # completion-order, incl. resumed history
    restarts: int = 0            # supervisor recoveries this fit
    recoveries: List[dict] = field(default_factory=list)
    # each: {"failure", "restored_to", "backoff_s", "restore_s"}

    def final_metric(self, n_episodes: int = 100) -> float:
        eps = self.episode_returns
        return float(eps[-n_episodes:].mean()) if len(eps) else float("nan")


class Trainer:
    """Periodic-checkpoint driver over any registered runtime.

    * ``ckpt_every``   — intervals per segment (0: one segment, checkpoint
      only at the end when ``checkpoint_dir`` is set).
    * ``on_segment``   — optional ``callback(intervals_done, RunResult)``
      invoked after each segment's checkpoint is durable; used by tests to
      simulate preemption (raising from it loses no committed work). Note
      intermediate segments run with ``finalize=False``, so their
      RunResult.params are mid-stream (one reporting update behind).
    * ``keep``         — how many most-recent checkpoints to retain
      (0 = keep all).
    * ``on_interval``  — optional reporting-only metrics observer,
      ``callback(interval, {"rewards": (alpha, n_envs), "dones": ...})``
      called once per completed interval (global index, so a resumed fit
      continues the numbering), after each segment returns — the
      streaming hook repro.api.Session threads through here.
    * ``faults``       — a ``FaultPlan`` or (shared) ``FaultInjector``.
      Arms two things: the ``checkpoint``-site truncation injection in
      ``_save``, and — when the plan's ``max_restarts > 0`` — the
      supervising loop (DESIGN.md §11): a failed segment (pool-guard
      RuntimeError, env exception, ``LearnerDiverged``) is absorbed by
      restoring the newest COMPLETE, uncorrupt checkpoint and replaying
      from it, with exponential backoff, up to ``max_restarts``
      CONSECUTIVE failures. Because ``run_from`` is bit-exact and
      injected events fire at most once, the recovered run's final
      params and episode-return stream equal the fault-free run's
      exactly (tests/test_faults.py). With ``faults=None`` (default)
      nothing changes: failures propagate as before this layer existed.
      Note one replay consequence: falling back PAST a corrupted newest
      checkpoint re-runs already-reported intervals, so ``on_interval``
      may see an index twice (identical metrics both times, by
      determinism); ``on_segment`` fires only after a durable save and
      is never replayed for an interval count it already saw, except in
      that same corrupt-fallback case.
    """

    def __init__(self, runtime: Runtime, checkpoint_dir: Optional[str] = None,
                 ckpt_every: int = 0,
                 on_segment: Optional[Callable[[int, Any], None]] = None,
                 keep: int = 3,
                 on_interval: Optional[Callable[[int, dict], None]] = None,
                 faults: Optional[FaultPlan | FaultInjector] = None):
        self.runtime = runtime
        self.checkpoint_dir = checkpoint_dir
        self.ckpt_every = ckpt_every
        self.on_segment = on_segment
        self.keep = keep
        self.on_interval = on_interval
        if faults is None or isinstance(faults, FaultInjector):
            self.faults = faults
        else:
            self.faults = FaultInjector(FaultPlan.of(faults))
        self._plan = self.faults.plan if self.faults is not None else None

    # ----------------------------------------------------------- ckpt io
    def _ckpt_path(self, intervals: int) -> str:
        return os.path.join(self.checkpoint_dir, f"step_{intervals:08d}")

    def latest_checkpoint(self) -> Optional[str]:
        if not self.checkpoint_dir:
            return None
        return ckpt_io.latest(self.checkpoint_dir)

    def _save(self, state: TrainState, intervals: int,
              stream: evaluate.ReturnStream) -> None:
        meta = checkpoint_metadata(self.runtime, intervals, stream)
        ckpt_io.save(self._ckpt_path(intervals), state, metadata=meta)
        if self.faults is not None:
            # checkpoint-site chaos: the atomic write (checkpoint/io)
            # makes a torn file impossible to PRODUCE, so the injectable
            # failure is post-write corruption — truncate the just-
            # written npz in place. Detected at restore as
            # CheckpointCorrupt; the supervisor falls back past it.
            ev = self.faults.poll("checkpoint", intervals)
            if ev is not None and ev.kind == "truncate":
                npz = self._ckpt_path(intervals) + ".npz"
                with open(npz, "r+b") as f:
                    size = f.seek(0, os.SEEK_END)
                    f.truncate(max(size // 2, 1))
        self._prune(intervals)

    def _prune(self, newest: int) -> None:
        prune_checkpoints(self.checkpoint_dir, self.keep)

    def _resume(self) -> tuple[Optional[TrainState], int, Optional[dict]]:
        path = self.latest_checkpoint()
        if path is None:
            return None, 0, None
        meta = ckpt_io.load_metadata(path)
        if meta.get("format") != CKPT_FORMAT:
            raise ValueError(
                f"{path} is not a trainer checkpoint "
                f"(format={meta.get('format')!r})")
        cfg = self.runtime.cfg
        # staleness defaults to 1 for checkpoints written before the
        # slab-ring generalization (their capsules ARE K=1 capsules)
        for key, have, default in (
                ("runtime", self.runtime.name, None),
                ("algorithm", cfg.algorithm, None), ("seed", cfg.seed, None),
                ("alpha", cfg.alpha, None), ("n_envs", cfg.n_envs, None),
                ("staleness", getattr(cfg, "staleness", 1), 1)):
            # runtime may legitimately differ (the capsule is
            # cross-runtime, tests/test_continuation.py) — warn-level
            # concerns are config fields that change the math
            if key != "runtime" and meta.get(key, default) != have:
                raise ValueError(
                    f"resume mismatch: checkpoint has {key}="
                    f"{meta.get(key, default)!r}, runtime has {have!r}")
        # batch geometry: a DIFFERENT factorization of the SAME global
        # batch is a supported restore (bit-exact by the determinism
        # contract, DESIGN.md §12) — announced loudly, never silent.
        # global_batch is pinned by the n_envs check above; checkpoints
        # written before BatchConfig carry no geometry (trivial default).
        geom = getattr(self.runtime, "geometry", None)
        saved = meta.get("batch")
        if (geom is not None and saved is not None
                and saved != geom.canonical()):
            print(f"[trainer] resume crosses batch geometries: "
                  f"checkpoint {saved} -> runtime {geom.canonical()} "
                  f"(same global_batch; bit-exact by the scale-out "
                  f"determinism contract)", file=sys.stderr)
        state = ckpt_io.restore(path, self.runtime.state())
        return state, int(meta["intervals"]), meta.get("metrics")

    # --------------------------------------------------------- recovery
    @staticmethod
    def _check_finite(params) -> None:
        for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
            a = np.asarray(jax.device_get(leaf))
            if np.issubdtype(a.dtype, np.floating) and \
                    not np.isfinite(a.astype(np.float32)).all():
                raise LearnerDiverged(
                    f"segment produced non-finite parameters (leaf {i})")

    def _recover(self, template, start0: int, entry_metrics):
        """Newest complete + UNCORRUPT checkpoint, walking past damaged
        ones loudly; ultimate fallback is the fit-entry capsule (replay
        everything this fit already ran). ``template`` is a host-side
        (numpy) snapshot of the entry capsule — deliberately NOT
        ``runtime.state()``: after a mid-interval failure the runtime's
        donated device buffers are not trustworthy."""
        if self.checkpoint_dir:
            for path in ckpt_io.complete_checkpoints(self.checkpoint_dir):
                meta = ckpt_io.load_metadata(path)
                if meta.get("format") != CKPT_FORMAT:
                    continue
                try:
                    state = ckpt_io.restore(path, template)
                except ckpt_io.CheckpointCorrupt as e:
                    print(f"[trainer] skipping corrupt checkpoint "
                          f"{os.path.basename(path)}: {e}",
                          file=sys.stderr)
                    continue
                return state, int(meta["intervals"]), meta.get("metrics")
        return (jax.tree_util.tree_map(jnp.asarray, template), start0,
                entry_metrics)

    # --------------------------------------------------------------- fit
    def fit(self, n_intervals: int, resume: bool = False) -> TrainReport:
        """Train until ``n_intervals`` TOTAL intervals have run (a resumed
        fit counts the checkpointed intervals toward the target)."""
        cfg = self.runtime.cfg
        if not resume and self.latest_checkpoint() is not None:
            # refusing beats the alternative: a fresh run interleaved
            # with stale checkpoints would let keep-k pruning delete the
            # NEW checkpoints while a later resume picks up the old run
            raise ValueError(
                f"{self.checkpoint_dir} already holds checkpoints "
                f"({os.path.basename(self.latest_checkpoint())}); pass "
                f"resume=True to continue that run, or point "
                f"checkpoint_dir at a fresh directory")
        state, start, metric_state = None, 0, None
        if resume:
            with spans.span(spans.FIT_RESUME):
                state, start, metric_state = self._resume()
        stream = evaluate.ReturnStream(cfg.n_envs)
        if metric_state is not None:
            stream.load_state_dict(metric_state)
        if state is None:
            state = self.runtime.state()   # fresh initial capsule
        plan = self._plan
        supervised = plan is not None and plan.max_restarts > 0
        if supervised:
            # host-side snapshot of the entry capsule: the restore
            # template and the ultimate fallback point. numpy copies —
            # immune to buffer donation by subsequent run_from calls.
            entry = jax.tree_util.tree_map(
                lambda a: np.asarray(jax.device_get(a)), state)
            entry_metrics = stream.state_dict()
        done = start
        out = None
        # committed segments as (done_after, rewards, dones, steps):
        # recovery to an older checkpoint truncates this list so the
        # reported reward/done arrays match the single surviving
        # timeline, bit-exactly — replayed segments replace, not append
        segs: list = []
        steps_executed = 0
        consec = 0
        restarts = 0
        recoveries: list = []
        t0 = time.perf_counter()
        while done < n_intervals:
            chunk = min(self.ckpt_every or (n_intervals - done),
                        n_intervals - done)
            try:
                # only the final segment pays the reporting-only trailing
                # learner pass; intermediate segments just stream metrics
                with spans.span(spans.FIT_SEGMENT):
                    out = self.runtime.run_from(
                        state, chunk, finalize=(done + chunk >= n_intervals))
                if plan is not None:
                    # BEFORE the capsule is saved: a diverged step must
                    # never become durable
                    self._check_finite(out.params)
            except Exception as e:
                if not supervised or consec >= plan.max_restarts:
                    raise
                consec += 1
                restarts += 1
                delay = min(plan.backoff * (2 ** (consec - 1)),
                            plan.backoff_cap)
                print(f"[trainer] segment at interval {done} failed "
                      f"({type(e).__name__}: {e}); restart "
                      f"{consec}/{plan.max_restarts} after "
                      f"{delay:.3f}s backoff", file=sys.stderr)
                time.sleep(delay)
                r0 = time.perf_counter()
                with spans.span(spans.FIT_RECOVER):
                    state, done, mstate = self._recover(
                        entry, start, entry_metrics)
                stream = evaluate.ReturnStream(cfg.n_envs)
                if mstate is not None:
                    stream.load_state_dict(mstate)
                segs = [s for s in segs if s[0] <= done]
                recoveries.append({
                    "failure": f"{type(e).__name__}: {e}",
                    "restored_to": done,
                    "backoff_s": delay,
                    "restore_s": time.perf_counter() - r0,
                })
                continue
            consec = 0
            with spans.span(spans.FIT_STREAM):
                if self.on_interval is not None:
                    for i, metrics in out.interval_metrics():
                        self.on_interval(done + i, metrics)
                stream.extend(out.rewards, out.dones)
            done += chunk
            with spans.span(spans.FIT_CAPTURE):
                state = self.runtime.state()
            segs.append((done, out.rewards, out.dones, out.steps))
            steps_executed += out.steps
            if self.checkpoint_dir:
                with spans.span(spans.FIT_SAVE):
                    self._save(state, done, stream)
            if self.on_segment is not None:
                self.on_segment(done, out)
        if out is None:
            # nothing left to run (resumed at or past the target):
            # report the restored state's parameters via a 0-segment
            out = self.runtime.run_from(state, 0)
        wall = time.perf_counter() - t0
        empty = np.zeros((0, cfg.alpha, cfg.n_envs), np.float32)
        rewards_log = [s[1] for s in segs]
        dones_log = [s[2] for s in segs]
        return TrainReport(
            params=out.params, state=state, intervals=done,
            resumed_from=start, steps=steps_executed, wall_time=wall,
            sps=steps_executed / max(wall, 1e-9),
            rewards=np.concatenate(rewards_log) if rewards_log else empty,
            dones=np.concatenate(dones_log) if dones_log else empty,
            episode_returns=stream.returns,
            restarts=restarts, recoveries=recoveries)
