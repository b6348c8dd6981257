"""Gridmaze84: gridmaze's rules on its fixed legacy board (``env_gridmaze``),
observed as the last four 84x84 grey frames, oldest first, one uint8
array (``repro.envs.gridmaze84``). Written plainly and batched over envs.
It takes no kwargs."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.env_gridmaze import GRID, GRID_HORIZON, _grid_walls
from bench.reference.envs import RefEnv, autoreset

SIZE = 84
FRAMES = 4
AGENT = 3


def make() -> RefEnv:
    """The gridmaze board and rules: start top-left, goal bottom-right,
    moves up/down/left/right, a blocked move stays put, +1 at the goal
    and -0.01 a step, the episode ends at the goal or after 36 steps.

    A frame shows the board at 84x84: pixel (i, j) shows cell
    ((i * 9) // 84, (j * 9) // 84), at level 0 for floor, 1 for a wall,
    2 for the goal and 3 where the agent stands. The observation holds
    the frames of the agent's last four positions, oldest first; after
    a reset all four show the start."""
    walls = jnp.asarray(_grid_walls())
    goal = (GRID - 1, GRID - 1)
    levels = jnp.where(walls > 0, 1, 0).at[goal].set(2)
    pixel_cell = (jnp.arange(SIZE) * GRID) // SIZE
    board = levels[pixel_cell][:, pixel_cell]
    moves = jnp.array([[-1, 0], [1, 0], [0, -1], [0, 1]], jnp.int32)

    def obs(state):
        # positions of the four frames, oldest first: (n, 4)
        rows = jnp.concatenate([state["pr"], state["r"][:, None]], axis=1)
        cols = jnp.concatenate([state["pc"], state["c"][:, None]], axis=1)
        agent = ((pixel_cell[None, :, None, None] == rows[:, None, None, :])
                 & (pixel_cell[None, None, :, None]
                    == cols[:, None, None, :]))
        return jnp.where(agent, AGENT, board[None, :, :, None]).astype(
            jnp.uint8)

    def reset(keys):
        n = keys.shape[0]
        z = jnp.zeros((n,), jnp.int32)
        h = jnp.zeros((n, FRAMES - 1), jnp.int32)
        state = {"r": z, "c": z, "t": z, "pr": h, "pc": h}
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
        ns = {"r": nr, "c": nc, "t": t,
              "pr": jnp.concatenate([state["pr"][:, 1:],
                                     state["r"][:, None]], axis=1),
              "pc": jnp.concatenate([state["pc"][:, 1:],
                                     state["c"][:, None]], axis=1)}
        return ns, obs(ns), reward, done

    def state_from_obs(o, t):
        # the agent's pixel in each frame, then the cell it shows
        n = o.shape[0]
        frames = jnp.moveaxis(o, -1, 1).reshape(n, FRAMES, SIZE * SIZE)
        flat = jnp.argmax(frames == AGENT, axis=-1)
        rows = pixel_cell[flat // SIZE].astype(jnp.int32)
        cols = pixel_cell[flat % SIZE].astype(jnp.int32)
        return {"r": rows[:, -1], "c": cols[:, -1],
                "t": jnp.asarray(t, jnp.int32),
                "pr": rows[:, :-1], "pc": cols[:, :-1]}

    return RefEnv(reset, autoreset(reset, inner_step), state_from_obs,
                  (SIZE, SIZE, FRAMES), 4)
