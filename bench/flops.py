"""Model FLOPs per env step, counted from shapes.

Only the multiply-adds of convolutions and dense layers count (2 FLOPs
each); elementwise work is left out. Per env step of HTS-RL training the
model runs: the actor's forward, the learner's forward and backward on
that step, and, once per interval of ``alpha`` steps, a forward of the
bootstrap observation (no backward: its value is a constant). The
backward of a layer costs its input gradient and its weight gradient,
each as much as its forward, except that no gradient flows into the
observation. Each policy counts its own layers in its reference file
(``bench/reference/net_<name>.py``), found by the policy's name.
"""
from __future__ import annotations

from pathlib import Path
from typing import List, Tuple

from bench.harness import BENCH_DIR
from bench.reference import nets


def layers(policy, obs_shape, n_actions, bench_dir: Path = BENCH_DIR
           ) -> List[Tuple[str, float]]:
    """(name, forward FLOPs for one observation) per layer, as the
    policy's reference file counts them (``net_<name>.layers``)."""
    return nets.load(policy["name"], bench_dir).layers(
        obs_shape, policy["arch"], n_actions)


def forward(policy, obs_shape, n_actions,
            bench_dir: Path = BENCH_DIR) -> float:
    return sum(f for _, f in layers(policy, obs_shape, n_actions,
                                    bench_dir))


def backward(policy, obs_shape, n_actions,
             bench_dir: Path = BENCH_DIR) -> float:
    """Weight gradients of every layer, input gradients of all but the
    first."""
    ls = layers(policy, obs_shape, n_actions, bench_dir)
    return 2 * sum(f for _, f in ls) - ls[0][1]


def train_step(policy, obs_shape, n_actions, alpha: int,
               bench_dir: Path = BENCH_DIR) -> float:
    """Model FLOPs per env step trained."""
    fwd = forward(policy, obs_shape, n_actions, bench_dir)
    return (fwd + fwd + backward(policy, obs_shape, n_actions, bench_dir)
            + fwd / alpha)

