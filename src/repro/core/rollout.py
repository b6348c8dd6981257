"""Vectorized executor/actor rollout (one synchronization interval).

``rollout_interval`` advances ``n_envs`` environment replicas ``alpha``
steps under a fixed behavior policy, producing the trajectory pytree the
learner consumes. Action sampling uses executor-derived keys
(core/determinism.py) so the result is independent of actor count and
batching — the jit'd equivalent of the paper's asynchronous
actor/executor interaction, which is *defined* to be
observation-order-independent.

``actor_forward`` is the single copy of the actor computation (policy
forward + per-observation-key sampling + behavior logprob); the threaded
host runtime batches racy observations through it while this module vmaps
it over a full interval — both paths produce bit-identical actions by the
determinism contract (DESIGN.md §3).

``env_offset`` shifts the env ids used for seed derivation: a data-parallel
shard holding replicas [offset, offset + n_envs) draws exactly the keys the
single-device run would for those envs, so sharding never changes the data.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import determinism, spans
from repro.envs.interfaces import Env


class RolloutConfig(NamedTuple):
    alpha: int                 # synchronization interval (steps)
    n_envs: int


def actor_forward(policy_apply: Callable, params, obs, keys):
    """The actor computation for one batch of observations.

    obs: (n, ...) stacked observations; keys: (n,) executor-attached PRNG
    keys. Returns (actions (n,) int, behavior_logprob (n,) f32). Which
    actor runs this, and how observations were batched, cannot affect the
    result: the key is a pure function of (run_seed, env_id, step).
    """
    with jax.named_scope(spans.ACTOR_FORWARD):
        logits, _ = policy_apply(params, obs)
        actions = jax.vmap(determinism.sample_action)(keys, logits)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        blp = jnp.take_along_axis(logp, actions[:, None], axis=-1)[:, 0]
    return actions, blp


def rollout_interval(policy_apply: Callable, env: Env, params, env_state,
                     obs, master_key, start_step, cfg: RolloutConfig,
                     env_offset=0):
    """Returns (traj, env_state', obs').

    traj = {obs, actions, rewards, dones, behavior_logprob: (alpha, n_envs),
            bootstrap_obs: (n_envs,)+obs_shape}.
    policy_apply(params, obs) -> (logits (n, A), value (n,)).
    env_offset: global id of this shard's first env replica (0 unless
    running data-parallel under shard_map).
    """
    env_ids = env_offset + jnp.arange(cfg.n_envs)

    def step(carry, t):
        env_state, obs = carry
        gstep = start_step + t
        keys = determinism.obs_keys(master_key, env_ids, gstep)
        actions, blp = actor_forward(policy_apply, params, obs, keys)
        step_keys = jax.vmap(
            lambda e: determinism.obs_key(master_key, e + 1_000_003, gstep)
        )(env_ids)
        with jax.named_scope(spans.ENV_STEP):
            env_state, next_obs, reward, done = env.step(env_state, actions,
                                                         step_keys)
        out = {"obs": obs, "actions": actions, "rewards": reward,
               "dones": done, "behavior_logprob": blp}
        return (env_state, next_obs), out

    (env_state, obs), traj = jax.lax.scan(
        step, (env_state, obs), jnp.arange(cfg.alpha))
    traj["bootstrap_obs"] = obs
    return traj, env_state, obs
