"""Run the HTS-RL training path end to end on a TPU and check what it
produces.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded path across 4 chips

The main path is the fused HTS-RL interval (rollout, learner and delayed
gradient in one XLA program), driven through ``repro.api`` Sessions:
runtime ``sharded`` on a one-chip mesh, env ``gridmaze`` on its
device-resident port, the paper's conv policy at its published widths
(filters 32/64/64, fc 512; 3x3 stride-1 kernels, which the 9x9 board
takes), a2c with rmsprop (lr 7e-4, eps 1e-5), alpha=5, n_envs=512,
staleness 1. Weights are random, made from the spec's seed.

Phases, in order; any failure exits nonzero and no phase catches its
own failure:

1. device  -- exit 1, naming the platform, unless JAX's first device is
              a TPU; there is no CPU fallback.
2. train   -- ``Session.fit`` for 6 intervals, checkpointing every 2
              under runs/chip_smoke/; time to the first checkpointed
              segment (compilation included), informational steady
              env-steps/s, peak device bytes, finiteness of every
              param. Then the same spec is built and run again, and
              the two params digests must be equal.
3. host    -- the same spec on the threaded ``host`` runtime and on
              ``sharded`` at n_envs=64 for 3 intervals; prints whether
              the digests agree and the largest param difference. A
              finding, not an assertion.
4. serve   -- ``Session.serve()`` from the phase-2 checkpoint answers 64
              requests (32 (obs, seed) pairs, each sent twice); a
              repeated pair must get the identical answer.

``--four-chips`` runs only the cross-chip path: the train spec sharded
over ``batch.n_replicas=4`` next to ``n_replicas=1``, in this process.
The 4-replica mesh must span 4 distinct TPU devices; the script reports
whether the two params digests are equal.

The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The phase functions take their sizes as arguments, so tests can run
them small on the CPU; ``main()`` always uses the sizes above.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.distributed import params_digest  # noqa: E402

RUNS = ROOT / "runs" / "chip_smoke"
N_ENVS = 512
INTERVALS = 6
CKPT_EVERY = 2
HOST_N_ENVS = 64
HOST_INTERVALS = 3
SERVE_PAIRS = 32


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def train_spec(n_envs: int = N_ENVS, runtime: str = "sharded",
               n_replicas: int = 1, ckpt_dir=None,
               ckpt_every: int = CKPT_EVERY) -> api.ExperimentSpec:
    """The main-path spec. Conv filters and the fc width are the paper's
    (configs/paper_cnn.py defaults); only the kernel geometry is set."""
    return api.ExperimentSpec(
        env="gridmaze",
        policy={"name": "cnn",
                "kwargs": {"conv_sizes": [3, 3, 3],
                           "conv_strides": [1, 1, 1]}},
        optimizer={"name": "rmsprop", "kwargs": {"lr": 7e-4, "eps": 1e-5}},
        algorithm="a2c",
        runtime=runtime,
        hts={"alpha": 5, "n_envs": n_envs, "staleness": 1,
             "env_backend": "device", "seed": 0},
        intervals=INTERVALS,
        checkpoint={"dir": None if ckpt_dir is None else str(ckpt_dir),
                    "every": ckpt_every},
        batch={"n_replicas": n_replicas} if runtime == "sharded" else None)


def _max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _n_params(params) -> int:
    return sum(int(np.size(x)) for x in jax.tree.leaves(params))


def _cache_entries(cache_dir: str) -> int:
    path = Path(cache_dir)
    return len(list(path.glob("*-cache"))) if path.is_dir() else 0


# ------------------------------------------------------------- phases
def check_device() -> dict:
    """Phase 1: refuse to run anywhere but on a TPU."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {dev.platform!r}, "
            f"device {dev.device_kind!r}); this script runs only on a TPU")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    print(f"[device] {info['kind']} x {info['count']} ({info['platform']})",
          flush=True)
    return info


def train_phase(n_envs: int = N_ENVS, intervals: int = INTERVALS,
                ckpt_every: int = CKPT_EVERY, runs: Path = RUNS) -> dict:
    """Phase 2: checkpointed fit, then an identical rerun whose params
    digest must match. Returns the fitted Session and the numbers."""
    shutil.rmtree(runs, ignore_errors=True)
    spec = train_spec(n_envs, ckpt_dir=runs / "train", ckpt_every=ckpt_every)
    session = api.build(spec)
    seg_ends = []
    t0 = time.perf_counter()
    report = session.fit(intervals, on_segment=lambda done, out:
                         seg_ends.append((done, time.perf_counter())))
    first_done, first_t = seg_ends[0]
    first_s = first_t - t0
    steps_per_interval = spec.hts["alpha"] * n_envs
    steady = None
    if len(seg_ends) > 1:
        rest = seg_ends[-1][0] - first_done
        steady = rest * steps_per_interval / (seg_ends[-1][1] - first_t)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    seg_s = np.diff([t0] + [t for _, t in seg_ends])
    finite = all(bool(np.isfinite(np.asarray(x)).all())
                 for x in jax.tree.leaves(report.params))
    digest = params_digest(report.params)
    print(f"[train] sharded x gridmaze(device) x cnn: "
          f"{_n_params(report.params)} params, n_envs={n_envs}, alpha=5, "
          f"{report.intervals} intervals, checkpoints every {ckpt_every}",
          flush=True)
    print(f"[train] seconds to the first {first_done} intervals "
          f"(compilation included): {first_s:.3f}", flush=True)
    print(f"[train] steady env-steps/s over intervals {first_done}.."
          f"{report.intervals - 1} (informational, checkpoint saves "
          f"included; not a metric): "
          + (f"{steady:.1f}" if steady is not None else "n/a"), flush=True)
    print(f"[train] seconds per checkpointed segment: "
          f"{[round(float(x), 3) for x in seg_s]}", flush=True)
    print(f"[train] peak_bytes_in_use: "
          f"{peak if peak is not None else 'not reported'} (of "
          f"bytes_limit {stats.get('bytes_limit', 'not reported')})",
          flush=True)
    print(f"[train] every param finite: {finite}", flush=True)
    _check(finite, "trained params are not all finite")

    rerun = api.build(train_spec(n_envs, ckpt_dir=runs / "rerun",
                                 ckpt_every=ckpt_every))
    again = rerun.fit(intervals)
    digest2 = params_digest(again.params)
    print(f"[train] params digest {digest}", flush=True)
    print(f"[train] rerun digest  {digest2} (bit-identical rerun: "
          f"{digest == digest2})", flush=True)
    _check(digest == digest2, "rerun of the same spec changed the params")
    return {"session": session, "digest": digest, "first_s": first_s,
            "steady_sps": steady, "peak_bytes": peak, "finite": finite}


def host_phase(n_envs: int = HOST_N_ENVS,
               intervals: int = HOST_INTERVALS) -> dict:
    """Phase 3: threaded host runtime vs the fused sharded program on
    the same spec. Reported, not asserted."""
    outs = {rt: api.build(train_spec(n_envs, runtime=rt)).run(intervals)
            for rt in ("host", "sharded")}
    digests = {rt: params_digest(o.params) for rt, o in outs.items()}
    equal = digests["host"] == digests["sharded"]
    diff = _max_abs_diff(outs["host"].params, outs["sharded"].params)
    print(f"[host] n_envs={n_envs}, {intervals} intervals: host digest "
          f"{digests['host'][:16]} sharded digest "
          f"{digests['sharded'][:16]} equal={equal} "
          f"max_abs_diff={diff!r}", flush=True)
    return {"equal": equal, "max_abs_diff": diff}


def serve_phase(session, pairs: int = SERVE_PAIRS) -> dict:
    """Phase 4: serve the checkpointed policy; every (obs, seed) pair is
    sent twice and must get the same answer both times."""
    n_obs = max(1, pairs // 4)
    obs = [np.asarray(session.env.reset(jax.random.key(i))[1])
           for i in range(n_obs)]
    requests = [(i % n_obs, 1000 + i // n_obs) for i in range(pairs)]
    server = session.serve()
    try:
        futures = [server.submit(obs[o], seed=s)
                   for _ in range(2) for o, s in requests]
        answers = [f.result(timeout=120) for f in futures]
        stats = server.stats()
    finally:
        server.stop()
    first, second = answers[:pairs], answers[pairs:]
    same = all((a.action, a.logprob) == (b.action, b.logprob)
               for a, b in zip(first, second))
    print(f"[serve] {len(answers)} requests answered "
          f"({pairs} (obs, seed) pairs x 2), "
          f"{len({a.action for a in answers})} distinct actions, "
          f"repeated pairs identical: {same}; stats {stats}", flush=True)
    _check(same, "a repeated (obs, seed) request got a different answer")
    return {"answered": len(answers), "identical": same}


def four_chip_phase(n_envs: int = N_ENVS,
                    intervals: int = INTERVALS) -> dict:
    """The cross-chip path: sharded over 4 replicas vs 1, same spec."""
    built = {r: api.build(train_spec(n_envs, n_replicas=r))
             for r in (4, 1)}
    # main() has already refused anything but a TPU as device 0
    platform = jax.devices()[0].platform
    mesh_devices = list(built[4].runtime.mesh.devices.flat)
    _check(len({d.id for d in mesh_devices}) == 4
           and all(d.platform == platform for d in mesh_devices),
           f"the 4-replica mesh does not span 4 distinct {platform} "
           f"devices: {mesh_devices}")
    print(f"[four-chips] 4-replica mesh devices: "
          f"{[(d.id, d.device_kind) for d in mesh_devices]}", flush=True)
    outs = {}
    for r, session in built.items():
        t0 = time.perf_counter()
        outs[r] = session.run(intervals)
        finite = all(bool(np.isfinite(np.asarray(x)).all())
                     for x in jax.tree.leaves(outs[r].params))
        print(f"[four-chips] n_replicas={r}: {intervals} intervals in "
              f"{time.perf_counter() - t0:.3f}s (compilation included), "
              f"params finite: {finite}", flush=True)
        _check(finite, f"n_replicas={r} params are not all finite")
    d4, d1 = (params_digest(outs[r].params) for r in (4, 1))
    diff = _max_abs_diff(outs[4].params, outs[1].params)
    # where the runs part: identical reward/done streams mean the
    # rollouts agree and only the learner's arithmetic differs
    same = [bool(np.array_equal(outs[4].rewards[i], outs[1].rewards[i])
                 and np.array_equal(outs[4].dones[i], outs[1].dones[i]))
            for i in range(intervals)]
    print(f"[four-chips] digest n_replicas=4 {d4[:16]} n_replicas=1 "
          f"{d1[:16]} equal={d4 == d1} max_abs_diff={diff!r}", flush=True)
    print(f"[four-chips] reward/done streams equal per interval: {same}",
          flush=True)
    return {"equal": d4 == d1, "max_abs_diff": diff,
            "streams_equal": same}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4-replica vs 1-replica "
                         "comparison (needs 4 TPU chips)")
    args = ap.parse_args(argv)

    device = check_device()
    if args.four_chips and device["count"] < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 TPU devices, "
                         f"found {device['count']}")
    cache_dir = enable_compile_cache()
    print(f"[cache] {cache_dir}: {_cache_entries(cache_dir)} entries "
          f"before the run", flush=True)
    if args.four_chips:
        four_chip_phase()
    else:
        trained = train_phase()
        host_phase()
        serve_phase(trained["session"])
    print(f"[cache] {cache_dir}: {_cache_entries(cache_dir)} entries "
          f"after the run", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
