"""Serving launcher — two modes, one command.

**Policy-as-a-service** (``--spec``): serve an RL policy from an
ExperimentSpec through the continuous-batching PolicyServer
(repro.serve, DESIGN.md §10), loading the newest TrainState checkpoint
capsule when the spec (or ``--checkpoint``) names one, then drive the
open-loop Poisson load generator against it and report p50/p99 + QPS:

    PYTHONPATH=src python -m repro.launch.serve \
        --spec examples/specs/quickstart.json \
        --checkpoint ckpts/step_00000040 --requests 500 --rate 2000

**LLM decode** (``--arch``, the historical mode): batched prefill +
per-token serve_step for any assigned arch:

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --reduced \
        --batch 4 --prompt-len 16 --gen 16

Both are the actor-side hot path of HTS-RL at scale, with the same
determinism contract as the RL actors: executor-style keys that are
pure functions of the request identity, so batch composition can never
change an answer.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import determinism, learner
from repro.launch.cache import enable_compile_cache
from repro.models import backbone


def serve_policy(args) -> None:
    """--spec mode: build the session, serve it, drive the load gen."""
    from repro import api
    from repro.serve import loadgen

    spec = api.load(args.spec)
    if args.max_batch is not None:
        spec = spec.replace(serve={"max_batch": args.max_batch,
                                   "max_queue": spec.serve.max_queue,
                                   "timeout_ms": spec.serve.timeout_ms})
    print(f"# serving {spec.env.name} x {spec.policy.name} "
          f"(max_batch={spec.serve.max_batch}, "
          f"checkpoint={args.checkpoint or spec.checkpoint.dir or 'none'})",
          flush=True)
    metrics = loadgen.run(spec, requests=args.requests, rate=args.rate,
                          seed=args.seed, checkpoint=args.checkpoint)
    for name, value in metrics.items():
        print(f"{name}={value:.6g}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", default=None, metavar="FILE",
                    help="serve an RL policy from this ExperimentSpec "
                         "JSON (policy-as-a-service mode)")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="with --spec: TrainState capsule base path "
                         "(default: latest under the spec's checkpoint "
                         "dir, else initial params)")
    ap.add_argument("--requests", type=int, default=500,
                    help="with --spec: load-generator request count")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="with --spec: offered load, req/s")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="with --spec: override the spec's "
                         "serve.max_batch")
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.spec:
        serve_policy(args)
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    B, S, G = args.batch, args.prompt_len, args.gen
    max_len = S + G

    params = backbone.init_params(cfg, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (B, S), 0,
                                 cfg.vocab_size)
    master = determinism.master_key(args.seed)

    kw = {}
    if cfg.is_encoder_decoder:
        kw["audio_embeds"] = jnp.zeros((B, cfg.enc_seq, cfg.d_model),
                                       jnp.bfloat16)
    if cfg.vision_prefix:
        kw["patch_embeds"] = jnp.zeros((B, cfg.vision_prefix, cfg.d_model),
                                       jnp.bfloat16)
    if cfg.mrope:
        kw["mrope_positions"] = jnp.broadcast_to(jnp.arange(S), (3, B, S))

    t0 = time.time()
    logits, _, cache = jax.jit(
        lambda p, t: backbone.prefill(p, cfg, t, max_len, **kw)
    )(params, prompts)
    print(f"prefill {B}x{S}: {time.time() - t0:.2f}s")

    serve = learner.make_serve_step(cfg)
    jserve = jax.jit(serve, donate_argnums=(2,))

    def pick(logits, step):
        if args.temperature <= 0:
            return jnp.argmax(logits, -1)
        keys = determinism.obs_keys(master, jnp.arange(B), step)
        return jax.vmap(determinism.sample_action)(
            keys, logits / args.temperature)

    tok = pick(logits, 0).astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for i in range(G - 1):
        extras = {}
        if cfg.mrope:
            extras["mrope_positions"] = jnp.full((3, B, 1), S + i)
        if cfg.is_encoder_decoder:
            extras["enc_out"] = jnp.zeros((B, cfg.enc_seq, cfg.d_model),
                                          jnp.bfloat16)
        logits, _, cache = jserve(params, tok[:, None], cache,
                                  jnp.int32(S + i), extras)
        tok = pick(logits, i + 1).astype(jnp.int32)
        out_tokens.append(tok)
    dt = time.time() - t0
    gen = jnp.stack(out_tokens, axis=1)
    print(f"decode {G - 1} steps: {dt:.2f}s "
          f"({B * (G - 1) / max(dt, 1e-9):.1f} tok/s)")
    print("generated:", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
