"""The seeding rules of the HTS-RL determinism contract, written out.

A sampled action's key is a pure function of (run seed, env id, global
step); an environment transition's key uses env id + 1,000,003. Large
seeds are folded in 32 bits at a time, so every whole number maps to its
own key.
"""
from __future__ import annotations

import jax

ENV_KEY_OFFSET = 1_000_003


def seed_key(seed: int):
    """A PRNG key for any non-negative whole number."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    high = seed >> 32
    while high:
        key = jax.random.fold_in(key, high & 0xFFFFFFFF)
        high >>= 32
    return key


def action_key(master, env_id, step):
    return jax.random.fold_in(jax.random.fold_in(master, env_id), step)


def env_key(master, env_id, step):
    return action_key(master, env_id + ENV_KEY_OFFSET, step)
