"""Gridmaze, the repo's pixel navigation task, on its fixed legacy board
(``repro.envs.gridmaze`` with no scenario): the rules written plainly
and batched over envs. It takes no kwargs."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from bench.reference.envs import RefEnv, autoreset

GRID = 9
GRID_HORIZON = 4 * GRID


def _grid_walls() -> np.ndarray:
    w = np.zeros((GRID, GRID), np.float32)
    w[2, 1:GRID - 2] = 1.0
    w[5, 2:GRID] = 1.0
    w[7, 1:4] = 1.0
    return w


def make() -> RefEnv:
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

    return RefEnv(reset, autoreset(reset, inner_step), state_from_obs,
                  (GRID, GRID, 3), 4)

