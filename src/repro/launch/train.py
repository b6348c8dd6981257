"""Training launcher: HTS-RL learner over any assigned architecture.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --reduced --steps 50 --batch 8 --seq 64

On this container it runs the reduced config on 1 CPU device; on a real
cluster the same code path pjit's over make_production_mesh() (pass
--mesh pod, requires the devices to exist).

Since the api redesign this launcher is a thin shell over the
declarative surface: the flags become an ``ExperimentSpec`` (env
``token_stream`` x policy ``backbone`` x the chosen optimizer/algorithm
x runtime ``stream``) and the loop is the engine-contract stream
runtime (core/stream_runtime.py) — the same ``learner.make_train_step``
pjit over the same stream batches, so losses are step-for-step
identical with the pre-api launcher, and checkpoints written by either
resume bit-exactly under the other (the checkpoint format — the
DelayedGradState plus arch/step metadata — is unchanged).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import api
from repro.checkpoint import io as ckpt_io
from repro.core import delayed_grad
from repro.core.engine import TrainState
from repro.launch.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--opt", default="adam", choices=["adam", "rmsprop"])
    # the token-trajectory learner implements only these two registry
    # algorithms (stale-correction algorithms need behavior-lagged
    # rollouts, which TokenStream does not produce)
    ap.add_argument("--algorithm", default="a2c", choices=["a2c", "ppo"])
    ap.add_argument("--mesh", default="host", choices=["host", "pod",
                                                       "multipod"])
    ap.add_argument("--checkpoint-dir", "--ckpt-dir", dest="ckpt_dir",
                    default=None)
    ap.add_argument("--ckpt-every", type=int, default=0, metavar="N",
                    help="save a checkpoint every N steps (0: only at "
                         "the end, when --checkpoint-dir is set)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in "
                         "--checkpoint-dir; bit-exact (the TokenStream "
                         "is fast-forwarded to the resumed step)")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()
    if args.ckpt_every and not args.ckpt_dir:
        ap.error("--ckpt-every requires --checkpoint-dir")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --checkpoint-dir")

    # the flags, as a declarative spec (api.save-able; the same
    # experiment runs under `python -m repro.launch.run --spec ...`).
    # The stream's vocab must match the (possibly reduced) model config.
    spec = api.ExperimentSpec(
        env={"name": "token_stream",
             "kwargs": {"vocab": _vocab_of(args), "batch": args.batch,
                        "seq": args.seq}},
        policy={"name": "backbone",
                "kwargs": {"arch": args.arch, "reduced": args.reduced}},
        optimizer={"name": args.opt, "kwargs": {"lr": args.lr}},
        algorithm=args.algorithm,
        runtime={"name": "stream", "kwargs": {"mesh": args.mesh}},
        intervals=args.steps)
    session = api.build(spec)

    start_step = 0
    state = None
    if args.resume:
        path = ckpt_io.latest(args.ckpt_dir)
        if path is not None:
            meta = ckpt_io.load_metadata(path)
            # anything that changes the update math or the data stream
            # must match, or "resume" would silently train a different
            # run (validate only keys the checkpoint recorded, for
            # compatibility with older checkpoints)
            for key, have in (("arch", args.arch),
                              ("algorithm", args.algorithm),
                              ("opt", args.opt), ("batch", args.batch),
                              ("seq", args.seq)):
                if key in meta and meta[key] != have:
                    raise SystemExit(
                        f"checkpoint {path} has {key}={meta[key]!r}, "
                        f"but this run was launched with {have!r}")
            dg = ckpt_io.restore(path, jax.eval_shape(
                lambda: delayed_grad.init(session.params, session.opt)))
            start_step = int(meta.get("step", meta.get("steps", 0)))
            state = TrainState(algo=dg, env_state={}, obs={}, buffer={},
                               interval=jnp.asarray(start_step, jnp.int32))
            print(f"resuming from {path} at step {start_step}", flush=True)
    if state is None:
        state = session.state()

    t0 = time.time()

    @session.on_interval
    def _log(m):
        i = m["interval"]
        if i % args.log_every == 0 or i == args.steps - 1:
            done = i - start_step + 1
            print(f"step {i:4d} loss={m['loss']:.4f} "
                  f"pg={m['pg']:.4f} "
                  f"ent={m['entropy']:.4f} "
                  f"({(time.time() - t0) / done:.3f}s/step)",
                  flush=True)

    def save_ckpt(state: TrainState, step: int) -> None:
        # the pre-api checkpoint format, unchanged: the DelayedGradState
        # alone (launch-specific metadata carries the step), so old and
        # new launchers resume each other's checkpoints
        ckpt_io.save(f"{args.ckpt_dir}/step_{step:08d}", state.algo,
                     {"arch": args.arch, "step": step,
                      "algorithm": args.algorithm, "opt": args.opt,
                      "batch": args.batch, "seq": args.seq})
        print(f"checkpoint -> {args.ckpt_dir}/step_{step:08d}",
              flush=True)

    done = start_step
    while done < args.steps:
        # segment to the next global ckpt-every multiple (matching the
        # pre-api launcher's checkpoint boundaries exactly)
        if args.ckpt_dir and args.ckpt_every:
            stop = min(((done // args.ckpt_every) + 1) * args.ckpt_every,
                       args.steps)
        else:
            stop = args.steps
        session.run_from(state, stop - done)
        state = session.state()
        done = stop
        if args.ckpt_dir and args.ckpt_every and done < args.steps:
            save_ckpt(state, done)
    if args.ckpt_dir and args.steps > start_step:
        save_ckpt(state, args.steps)


def _vocab_of(args) -> int:
    """The (possibly reduced) model config's vocab size — what the
    token stream must emit."""
    from repro.configs.base import get_config
    cfg = get_config(args.arch)
    return (cfg.reduced() if args.reduced else cfg).vocab_size


if __name__ == "__main__":
    main()
