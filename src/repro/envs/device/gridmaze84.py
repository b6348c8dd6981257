"""Device-resident gridmaze84: the batched port of ``repro.envs.gridmaze84``.

State layout: ``{"r", "c", "t"}``, each ``(n,)`` int32, and ``{"pr",
"pc"}``, each ``(n, FRAMES - 1)`` int32: the stacked pytree of the
vmapped host env, capsule-compatible across backends.

The rules are gridmaze's batched ``move``. The frames are built with no
scatter: the agent's pixels are where the static pixel-to-cell index
equals its row and its column (broadcast comparisons, one per frame of
the stack), laid over the static board upsampled once at build time.
Rendering sits in the ``render`` device scope, inside ``env_step``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.envs.device import DeviceEnv, device_autoreset
from repro.envs.gridmaze import move
from repro.envs.gridmaze84 import (AGENT, FRAMES, GOAL, PIXEL_CELL, SIZE,
                                   WALLS, board_levels, shift_history)


def _batched_fns():
    cell = jnp.asarray(PIXEL_CELL, jnp.int32)
    board = jnp.asarray(board_levels()[PIXEL_CELL][:, PIXEL_CELL])

    def render(state):
        with jax.named_scope(spans.RENDER):
            rows = jnp.concatenate([state["pr"], state["r"][:, None]], 1)
            cols = jnp.concatenate([state["pc"], state["c"][:, None]], 1)
            in_row = cell[None, :, None] == rows[:, None, :]  # (n, 84, F)
            in_col = cell[None, :, None] == cols[:, None, :]
            agent = in_row[:, :, None, :] & in_col[:, None, :, :]
            return jnp.where(agent, jnp.uint8(AGENT),
                             board[None, :, :, None])

    def reset(keys):
        n = keys.shape[0]
        zeros = jnp.zeros((n,), jnp.int32)
        hist = jnp.zeros((n, FRAMES - 1), jnp.int32)
        # distinct buffers per leaf: the engine donates the carry
        # (envs/device/gridmaze.py)
        state = {"r": zeros, "c": jnp.copy(zeros), "t": jnp.copy(zeros),
                 "pr": hist, "pc": jnp.copy(hist)}
        return state, render(state)

    def step(state, actions, keys):
        del keys
        nr, nc, t, reward, done = move(WALLS, GOAL, state["r"], state["c"],
                                       state["t"], actions)
        pr, pc = shift_history(state)
        ns = {"r": nr, "c": nc, "t": t, "pr": pr, "pc": pc}
        return ns, render(ns), reward, done.astype(jnp.float32)

    return reset, step


def make() -> DeviceEnv:
    reset, step = _batched_fns()
    return device_autoreset("gridmaze84@device", reset, step,
                            (SIZE, SIZE, FRAMES), 4, host_name="gridmaze84")
