"""The environments' rules, written plainly and batched over envs.

Each env gives ``reset(keys)``, ``step(state, actions, keys)`` (with the
auto-reset the HTS-RL executors apply: on done, the returned state and
observation are the next episode's first, drawn with ``fold_in(key, 7)``)
and ``state_from_obs(obs, t)``, which rebuilds the full state from an
observation and the steps since the episode began, so a recorded
trajectory can be stepped again transition by transition.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp


class RefEnv(NamedTuple):
    reset: Callable
    step: Callable
    state_from_obs: Callable
    obs_shape: tuple
    n_actions: int


def _autoreset(reset, inner_step):
    def step(state, actions, keys):
        ns, obs, r, done = inner_step(state, actions, keys)
        rs, robs = reset(jax.vmap(lambda k: jax.random.fold_in(k, 7))(keys))
        pick = lambda a, b: jnp.where(
            done.reshape(done.shape + (1,) * (a.ndim - 1)) > 0, b, a)
        return (jax.tree.map(pick, ns, rs), pick(obs, robs), r, done)
    return step


# ----------------------------------------------------------- gridmaze
GRID = 9
GRID_HORIZON = 4 * GRID


def _grid_walls() -> np.ndarray:
    w = np.zeros((GRID, GRID), np.float32)
    w[2, 1:GRID - 2] = 1.0
    w[5, 2:GRID] = 1.0
    w[7, 1:4] = 1.0
    return w


def gridmaze() -> RefEnv:
    """9x9 board with the fixed wall pattern, start top-left, goal
    bottom-right; moves up/down/left/right, a blocked move stays put;
    +1 at the goal, -0.01 a step; the episode ends at the goal or after
    36 steps. Observation planes: walls, agent, goal."""
    walls = jnp.asarray(_grid_walls())
    goal = (GRID - 1, GRID - 1)
    goal_plane = jnp.zeros((GRID, GRID)).at[goal].set(1.0)
    moves = jnp.array([[-1, 0], [1, 0], [0, -1], [0, 1]], jnp.int32)
    rows = jnp.arange(GRID)

    def obs(state):
        agent = ((rows[None, :, None] == state["r"][:, None, None])
                 & (rows[None, None, :] == state["c"][:, None, None]))
        n = state["r"].shape[0]
        return jnp.stack([jnp.broadcast_to(walls, (n, GRID, GRID)),
                          agent.astype(jnp.float32),
                          jnp.broadcast_to(goal_plane, (n, GRID, GRID))],
                         axis=-1)

    def reset(keys):
        n = keys.shape[0]
        z = jnp.zeros((n,), jnp.int32)
        state = {"r": z, "c": z, "t": z}
        return state, obs(state)

    def inner_step(state, actions, keys):
        mv = moves[actions]
        nr = jnp.clip(state["r"] + mv[:, 0], 0, GRID - 1)
        nc = jnp.clip(state["c"] + mv[:, 1], 0, GRID - 1)
        blocked = walls[nr, nc] > 0
        nr = jnp.where(blocked, state["r"], nr)
        nc = jnp.where(blocked, state["c"], nc)
        t = state["t"] + 1
        at_goal = (nr == goal[0]) & (nc == goal[1])
        done = (at_goal | (t >= GRID_HORIZON)).astype(jnp.float32)
        reward = jnp.where(at_goal, 1.0, -0.01).astype(jnp.float32)
        ns = {"r": nr, "c": nc, "t": t}
        return ns, obs(ns), reward, done

    def state_from_obs(o, t):
        flat = jnp.argmax(o[..., 1].reshape(o.shape[0], -1), axis=-1)
        return {"r": (flat // GRID).astype(jnp.int32),
                "c": (flat % GRID).astype(jnp.int32),
                "t": jnp.asarray(t, jnp.int32)}

    return RefEnv(reset, _autoreset(reset, inner_step), state_from_obs,
                  (GRID, GRID, 3), 4)


ENVS = {"gridmaze": gridmaze}


def make(name: str) -> RefEnv:
    try:
        return ENVS[name]()
    except KeyError:
        raise ValueError(f"no reference for env {name!r}; have "
                         f"{sorted(ENVS)}") from None
