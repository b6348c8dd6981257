"""Training cells: HTS-RL through ``Session.fit`` with checkpointing on.

The traffic file gives the runtime and sizes (``spec``), the checkpoint
period ``ckpt_every`` (intervals per segment, one save each),
``intervals_per_s``, which fixes the window's work: the window trains
``seconds * intervals_per_s`` intervals, rounded up to whole segments,
and optionally ``reports``, the end-to-end metric the window's rate of
env steps is reported under (``env_steps_per_s`` by default).

Set-up builds one Session with the benchmark's weights and drives it
from the seed through its first four intervals, one ``fit`` call and one
checkpoint each (a segment hands back the trajectory of its last
interval only, so one interval per segment is what lets the reference
see every interval); each records the trajectory, RMSProp's state and
the parameters, and the capsule after the fourth is kept. It then runs
two segments of the window's length, which compiles the window's
programs. The window is one ``fit`` call that resumes from the last
checkpoint.

Once the window has closed, the same Session ties the window's segment
program to the one-interval program the reference checks: from the kept
capsule it runs one segment of ``ckpt_every`` intervals, and
``ckpt_every`` segments of one interval, and counts the bits in which the
two end states and reward/done streams differ (``segment_mismatch``;
the runtimes guarantee that any partition into segments is bit-exact).
The Session is then freed and the reference replays the first three
learner steps (``bench.reference.hts.check``).
"""
from __future__ import annotations

import gc
import math
import shutil
import time

import jax
import numpy as np

from bench import device as device_mod
from bench import flops
from bench import session as S
from bench.reference import envs as ref_envs
from bench.reference import hts
from bench.reference import nets

CHECK_INTERVALS = 4


def first_steps(cell, seed: int, run_dir, profile: bool = False):
    """Build the cell's Session with the weights of ``seed`` and drive
    it through the first ``CHECK_INTERVALS`` intervals, one checkpointed
    ``fit`` segment each. Returns (spec, env, weights on the host, the
    record the check reads, the Session, its capsule after them on the
    host)."""
    spec = cell.spec_dict()
    shutil.rmtree(run_dir, ignore_errors=True)
    spec["checkpoint"] = {"dir": str(run_dir / "ckpt"),
                          "every": int(cell.traffic["ckpt_every"]),
                          "keep": 2}
    env = ref_envs.make(spec["env"]["name"], spec["env"].get("kwargs", {}),
                        cell.bench_dir)
    params = S.make_weights(spec, env.obs_shape, env.n_actions, seed,
                            cell.bench_dir)
    params0 = S.host(params)
    runtime_kwargs = {}
    if spec["runtime"]["name"] == "host":
        from repro.core.host_runtime import HostConfig
        runtime_kwargs["host"] = HostConfig(**cell.traffic["host"],
                                            profile=profile)
    session = S.build(spec, params, runtime_kwargs)
    rec = []

    def capture(done, out):
        st = session.state()
        rec.append({"traj": S.host(st.buffer),
                    "sq": S.host(st.algo.opt_state["sq"]),
                    "params": S.host(st.algo.params)})

    for n in range(1, CHECK_INTERVALS + 1):
        session.fit(n, resume=n > 1, on_segment=capture)
    return spec, env, params0, rec, session, S.host(session.state())


def _bits_differ(a, b) -> int:
    """Elements of two arrays whose bits differ."""
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return max(a.size, b.size, 1)
    if a.size == 0:
        return 0
    va = a.reshape(a.size, 1).view(np.uint8)
    vb = b.reshape(b.size, 1).view(np.uint8)
    return int(np.count_nonzero(np.any(va != vb, axis=1)))


def segment_tie(runtime, capsule, every: int) -> int:
    """From ``capsule``, one segment of ``every`` intervals against
    ``every`` segments of one: the elements of the end capsule and of
    the reward/done streams whose bits differ."""
    long = runtime.run_from(capsule, every, finalize=False)
    ends = [S.host(runtime.state())]
    state, rewards, dones = capsule, [], []
    for _ in range(every):
        out = runtime.run_from(state, 1, finalize=False)
        state = S.host(runtime.state())
        rewards.append(out.rewards)
        dones.append(out.dones)
    ends.append(state)
    a, b = (jax.tree.leaves(e) + [r, d] for e, r, d in (
        (ends[0], long.rewards, long.dones),
        (ends[1], np.concatenate(rewards), np.concatenate(dones))))
    if len(a) != len(b):
        return sum(x.size for x in a + b)
    return sum(_bits_differ(x, y) for x, y in zip(a, b))


def check(spec, env, params0, rec, bench_dir) -> dict:
    """The reference's replay of a record (``hts.check``)."""
    _, apply = nets.make(S.ref_policy(spec), env.obs_shape, env.n_actions,
                         bench_dir)
    return hts.check(apply, env, params0, S.ref_hp(spec), rec)


def window_intervals(traffic: dict, seconds: float) -> int:
    """The window's work: ``seconds * intervals_per_s`` intervals,
    rounded up to whole segments of ``ckpt_every``."""
    every = int(traffic["ckpt_every"])
    return every * max(1, math.ceil(
        seconds * traffic["intervals_per_s"] / every))


def run(ctx) -> dict:
    cell, seed, seconds = ctx["cell"], ctx["seed"], ctx["seconds"]
    traffic = cell.traffic
    run_dir = ctx["run_dir"]
    every = int(traffic["ckpt_every"])
    # ---- set-up: the check's first steps, then the window's program
    spec, env, params0, rec, session, capsule = first_steps(
        cell, seed, run_dir, profile=ctx["trace"])
    # two segments, as the window runs them: the first resumes from a
    # checkpoint, the next continues from the runtime's own state, the
    # last ends with the reporting drain; each may be its own program
    done = CHECK_INTERVALS + 2 * every
    session.fit(done, resume=True)

    # ---- window
    hp = S.ref_hp(spec)
    n_envs, alpha = spec["hts"]["n_envs"], hp["alpha"]
    intervals = window_intervals(traffic, seconds)
    walls, profile = [], {}

    def on_segment(d, out):
        walls.append(out.wall_time)
        for k, v in (getattr(session.runtime, "profile", None) or {}).items():
            profile[k] = profile.get(k, 0.0) + v

    setup_s = time.time() - ctx["t_proc"]
    with S.window(ctx["trace_dir"], ctx["counter"]) as w:
        t0 = time.perf_counter()
        session.fit(done + intervals, resume=True, on_segment=on_segment)
        t1 = time.perf_counter()
    compiles = ctx["counter"].count
    peak = device_mod.memory_peak_bytes(ctx["devices"])

    # ---- correctness, once the window has closed
    t_check = time.perf_counter()
    mismatch = segment_tie(session.runtime, capsule, every)
    del session
    gc.collect()
    shutil.rmtree(run_dir, ignore_errors=True)
    numbers = {"segment_mismatch": mismatch,
               **check(spec, env, params0, rec, cell.bench_dir)}
    check_s = time.perf_counter() - t_check

    window_s = t1 - t0
    rate = intervals * alpha * n_envs / window_s
    return {
        "e2e": {traffic.get("reports", "env_steps_per_s"): rate,
                "setup_s": setup_s},
        "window_s": window_s,
        "intervals": intervals,
        "segments": len(walls),
        "program_s": sum(walls),
        "host_profile": profile or None,
        "env_steps_per_s": rate,
        "flops_per_step": flops.train_step(S.ref_policy(spec),
                                           env.obs_shape, env.n_actions,
                                           alpha, cell.bench_dir),
        "device_kind": ctx["devices"][0].device_kind,
        "kind": "fit",
        "chips": len(ctx["devices"]),
        "trace": w.get("trace"),
        "compiles_in_window": compiles,
        "memory_peak_bytes": peak,
        "attempted": intervals,
        "failed": 0,
        "numbers": numbers,
        "notes": [f"window: {intervals} intervals in {len(walls)} "
                  f"segments of {every}, {window_s:.3f} s",
                  f"check: {check_s:.3f} s"],
    }
