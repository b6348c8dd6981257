"""GridMaze: deterministic navigation with pixel observations (Atari-like
horizon/credit structure, fully deterministic transition function).

N x N grid with a wall pattern; agent starts top-left. Actions:
up/down/left/right. Reward: +1 at goal, -0.01 per step. Horizon 4*N.
Observation: (N, N, 3) image (walls, agent, goal).

Two scenario sources:

  * default (``scenario_seed=None``) — the fixed legacy board: walls
    ``WALLS``, goal bottom-right. The goldens' board.
  * ``scenario_seed=k`` — a procedurally sampled board from
    ``sample_scenario(k)``: a pure numpy function of the seed alone
    (wall segments + BFS solvability check + deterministic farthest-
    reachable goal), shared verbatim by the device port — so host and
    device backends of the same seed see bit-identical static boards,
    and pool tenants (repro.tenancy) each train on a distinct
    deterministic scenario by seed.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.envs.interfaces import Env, with_autoreset

N = 9
HORIZON = 4 * N


def _walls():
    w = jnp.zeros((N, N), jnp.float32)
    w = w.at[2, 1:N - 2].set(1.0)
    w = w.at[5, 2:N].set(1.0)
    w = w.at[7, 1:4].set(1.0)
    return w


WALLS = _walls()
MOVES = jnp.array([[-1, 0], [1, 0], [0, -1], [0, 1]], jnp.int32)


def _bfs_dist(walls: np.ndarray) -> np.ndarray:
    """Grid distances from (0, 0) through open cells; -1 = unreachable."""
    dist = np.full((N, N), -1, np.int32)
    if walls[0, 0] > 0:
        return dist
    dist[0, 0] = 0
    frontier = [(0, 0)]
    while frontier:
        nxt = []
        for r, c in frontier:
            for dr, dc in ((-1, 0), (1, 0), (0, -1), (0, 1)):
                rr, cc = r + dr, c + dc
                if 0 <= rr < N and 0 <= cc < N and walls[rr, cc] == 0 \
                        and dist[rr, cc] < 0:
                    dist[rr, cc] = dist[r, c] + 1
                    nxt.append((rr, cc))
        frontier = nxt
    return dist


def sample_scenario(seed: int) -> Tuple[np.ndarray, Tuple[int, int]]:
    """Sample a solvable (walls, goal) board as a PURE function of the
    seed: numpy-only, no global state, no backend involvement — which
    is what makes host and device ports of the same seed bit-identical
    by construction. Rejection-samples wall layouts until the farthest
    BFS-reachable cell is at least N steps from the start (a
    nontrivially-deep maze); the goal is that farthest cell, row-major
    tie-break via argmax."""
    rng = np.random.default_rng(int(seed))
    while True:
        walls = np.zeros((N, N), np.float32)
        for _ in range(3 + int(rng.integers(0, 3))):   # 3..5 segments
            horiz = bool(rng.integers(0, 2))
            r = int(rng.integers(1, N - 1))
            c = int(rng.integers(1, N - 1))
            length = int(rng.integers(3, N - 1))
            if horiz:
                walls[r, c:min(c + length, N)] = 1.0
            else:
                walls[r:min(r + length, N), c] = 1.0
        walls[0, 0] = 0.0
        dist = _bfs_dist(walls)
        dist[0, 0] = -1                    # the start is never the goal
        if dist.max() < N:
            continue                       # too shallow/unsolvable: reject
        goal = np.unravel_index(int(dist.argmax()), dist.shape)
        return walls, (int(goal[0]), int(goal[1]))


def move(walls: jnp.ndarray, goal: Tuple[int, int], r, c, t, action):
    """The rules of one step from ``(r, c)`` at step ``t``, for one env
    or a batch alike: move, or stay put where the move is blocked; +1 at
    the goal, -0.01 otherwise; done (a bool) at the goal or at the
    horizon. Returns ``(r, c, t, reward, done)``."""
    mv = MOVES[action]
    nr = jnp.clip(r + mv[..., 0], 0, N - 1)
    nc = jnp.clip(c + mv[..., 1], 0, N - 1)
    blocked = walls[nr, nc] > 0
    nr = jnp.where(blocked, r, nr)
    nc = jnp.where(blocked, c, nc)
    t = t + 1
    at_goal = (nr == goal[0]) & (nc == goal[1])
    done = at_goal | (t >= HORIZON)
    reward = jnp.where(at_goal, 1.0, -0.01)
    return nr, nc, t, reward, done


def _scalar_fns(walls: jnp.ndarray, goal: Tuple[int, int]):
    """The scalar reset/step pair over one (walls, goal) board."""
    gr, gc = goal
    goal_plane = jnp.zeros((N, N), jnp.float32).at[gr, gc].set(1.0)

    def obs(state):
        agent = jnp.zeros((N, N), jnp.float32) \
            .at[state["r"], state["c"]].set(1.0)
        return jnp.stack([walls, agent, goal_plane], axis=-1)

    def reset(key):
        del key
        state = {"r": jnp.zeros((), jnp.int32),
                 "c": jnp.zeros((), jnp.int32),
                 "t": jnp.zeros((), jnp.int32)}
        return state, obs(state)

    def step(state, action, key):
        del key
        nr, nc, t, reward, done = move(walls, goal, state["r"], state["c"],
                                       state["t"], action)
        ns = {"r": nr, "c": nc, "t": t}
        return ns, obs(ns), reward, done.astype(jnp.float32)

    return reset, step


def resolve_board(scenario_seed: Optional[int]):
    """(walls, goal) for a scenario seed; None = the legacy board."""
    if scenario_seed is None:
        return WALLS, (N - 1, N - 1)
    walls, goal = sample_scenario(scenario_seed)
    return jnp.asarray(walls), goal


def make(scenario_seed: Optional[int] = None) -> Env:
    walls, goal = resolve_board(scenario_seed)
    reset, step = _scalar_fns(walls, goal)
    kwargs = (None if scenario_seed is None
              else {"scenario_seed": int(scenario_seed)})
    return with_autoreset("gridmaze", reset, step, (N, N, 3), 4,
                          make_kwargs=kwargs)
