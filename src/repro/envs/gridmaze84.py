"""GridMaze84: gridmaze's legacy board seen as stacked Atari-size frames.

The rules are gridmaze's own (``repro.envs.gridmaze.move`` on its fixed
board: walls ``WALLS``, goal (8, 8), +1 at the goal, -0.01 a step,
horizon 36). Only the observation differs: the last ``FRAMES`` grey
frames, oldest first, as one ``(84, 84, 4)`` uint8 array, the frame size
and stack depth that the paper's conv trunk takes (DQN / IMPALA Atari
preprocessing). Pixel row ``i`` shows board row ``(i * 9) // 84``, and
columns the same way (nearest neighbour, 9-10 pixels a cell). Levels:
floor 0, wall 1, goal 2, agent 3, unscaled, so the policy sees values in
[0, 3].

State: gridmaze's ``(r, c, t)`` plus ``pr``/``pc``, the agent's
``FRAMES - 1`` previous positions, oldest first. At reset every frame
shows the start.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.envs.gridmaze import N, move, resolve_board
from repro.envs.interfaces import Env, with_autoreset

SIZE = 84
FRAMES = 4
FLOOR, WALL, GOAL_LEVEL, AGENT = 0, 1, 2, 3
WALLS, GOAL = resolve_board(None)      # gridmaze's legacy board
# the board cell each pixel row (and column) shows
PIXEL_CELL = (np.arange(SIZE) * N) // SIZE


def board_levels() -> np.ndarray:
    """The static board's levels, (N, N) uint8: floor, walls, goal."""
    board = np.where(np.asarray(WALLS) > 0, WALL, FLOOR).astype(np.uint8)
    board[GOAL] = GOAL_LEVEL
    return board


def shift_history(state: dict) -> tuple:
    """``(pr, pc)`` after a step from ``state``: the oldest position
    dropped, the one the agent stepped from appended."""
    return (jnp.concatenate([state["pr"][..., 1:], state["r"][..., None]],
                            axis=-1),
            jnp.concatenate([state["pc"][..., 1:], state["c"][..., None]],
                            axis=-1))


def _scalar_fns():
    board = jnp.asarray(board_levels())
    cell = jnp.asarray(PIXEL_CELL)

    def frame(r, c):
        return board.at[r, c].set(AGENT)[cell][:, cell]

    def obs(state):
        rows = jnp.append(state["pr"], state["r"])
        cols = jnp.append(state["pc"], state["c"])
        return jax.vmap(frame, out_axes=-1)(rows, cols)

    def reset(key):
        del key
        z = jnp.zeros((), jnp.int32)
        h = jnp.zeros((FRAMES - 1,), jnp.int32)
        state = {"r": z, "c": z, "t": z, "pr": h, "pc": h}
        return state, obs(state)

    def step(state, action, key):
        del key
        nr, nc, t, reward, done = move(WALLS, GOAL, state["r"], state["c"],
                                       state["t"], action)
        pr, pc = shift_history(state)
        ns = {"r": nr, "c": nc, "t": t, "pr": pr, "pc": pc}
        return ns, obs(ns), reward, done.astype(jnp.float32)

    return reset, step


def make() -> Env:
    reset, step = _scalar_fns()
    return with_autoreset("gridmaze84", reset, step, (SIZE, SIZE, FRAMES),
                          4)
