"""Device-resident gridmaze: the batched port of ``repro.envs.gridmaze``.

State layout: ``{"r", "c", "t"}``, each ``(n,)`` int32 — the stacked
pytree of the vmapped host env, capsule-compatible across backends.

Fully deterministic, so the whole step is broadcast arithmetic: the
host env's own rules (``gridmaze.move``) run batched — moves are a
gather from the shared MOVES table, wall collisions a batched
advanced-index lookup into the walls board — and the 3-channel
observation is assembled from one-hot comparison masks plus broadcast
copies of the static walls/goal planes — no scatter anywhere.

Procedural scenarios: ``make(scenario_seed=k)`` resolves the SAME
``sample_scenario(k)`` board as the host factory (one pure numpy
function of the seed, repro.envs.gridmaze), so both backends of a
seeded scenario are bit-identical by construction — the equivalence
suite then pins the dynamic streams too (tests/test_device_envs.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.envs.gridmaze import N, move, resolve_board
from repro.envs.device import DeviceEnv, device_autoreset


def _batched_fns(walls: jnp.ndarray, goal):
    gr, gc = goal
    goal_plane = jnp.zeros((N, N), jnp.float32).at[gr, gc].set(1.0)

    def obs(state):
        rows = (state["r"][:, None]
                == jnp.arange(N, dtype=jnp.int32)).astype(jnp.float32)
        cols = (state["c"][:, None]
                == jnp.arange(N, dtype=jnp.int32)).astype(jnp.float32)
        agent = rows[:, :, None] * cols[:, None, :]
        n = state["r"].shape[0]
        walls_b = jnp.broadcast_to(walls, (n, N, N))
        goal_b = jnp.broadcast_to(goal_plane, (n, N, N))
        return jnp.stack([walls_b, agent, goal_b], axis=-1)

    def reset(keys):
        n = keys.shape[0]
        zeros = jnp.zeros((n,), jnp.int32)
        # distinct buffers per leaf: the engine donates the carry, and
        # XLA rejects donating one buffer under several leaves (eager
        # jnp.zeros is constant-cached, so three names would share one)
        state = {"r": zeros, "c": jnp.copy(zeros), "t": jnp.copy(zeros)}
        return state, obs(state)

    def step(state, actions, keys):
        del keys
        nr, nc, t, reward, done = move(walls, goal, state["r"], state["c"],
                                       state["t"], actions)
        ns = {"r": nr, "c": nc, "t": t}
        return ns, obs(ns), reward, done.astype(jnp.float32)

    return reset, step


def make(scenario_seed: Optional[int] = None) -> DeviceEnv:
    walls, goal = resolve_board(scenario_seed)
    reset, step = _batched_fns(walls, goal)
    return device_autoreset("gridmaze@device", reset, step, (N, N, 3), 4,
                            host_name="gridmaze")
