"""The environments' rules, written plainly and batched over envs.

Each env's reference is a file of its own, ``env_<name>.py`` beside
this one, whose ``make(**kwargs)`` takes the configuration's env
``kwargs`` and returns a ``RefEnv``; ``make(name, kwargs)`` finds it by
name. Each env gives ``reset(keys)``, ``step(state, actions, keys)``
(with the auto-reset the HTS-RL executors apply, ``autoreset``: on done,
the returned state and observation are the next episode's first, drawn
with ``fold_in(key, 7)``) and ``state_from_obs(obs, t)``, which rebuilds
the full state from an observation and the steps since the episode
began, so a recorded trajectory can be stepped again transition by
transition.
"""
from __future__ import annotations

from pathlib import Path
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from bench.harness import BENCH_DIR, load_file


class RefEnv(NamedTuple):
    reset: Callable
    step: Callable
    state_from_obs: Callable
    obs_shape: tuple
    n_actions: int


def autoreset(reset, inner_step):
    """``step`` with the executors' auto-reset around ``inner_step``."""
    def step(state, actions, keys):
        ns, obs, r, done = inner_step(state, actions, keys)
        rs, robs = reset(jax.vmap(lambda k: jax.random.fold_in(k, 7))(keys))
        pick = lambda a, b: jnp.where(
            done.reshape(done.shape + (1,) * (a.ndim - 1)) > 0, b, a)
        return (jax.tree.map(pick, ns, rs), pick(obs, robs), r, done)
    return step


def make(name: str, kwargs: Optional[dict] = None,
         bench_dir: Path = BENCH_DIR) -> RefEnv:
    """The reference of env ``name`` (``reference/env_<name>.py`` under
    ``bench_dir``), made with the configuration's env ``kwargs``."""
    return load_file("reference", name, bench_dir,
                     prefix="env_").make(**(kwargs or {}))
