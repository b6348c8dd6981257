"""Model FLOPs per env step, counted from shapes.

Only the multiply-adds of convolutions and dense layers count (2 FLOPs
each); elementwise work is left out. Per env step of HTS-RL training the
model runs: the actor's forward, the learner's forward and backward on
that step, and, once per interval of ``alpha`` steps, a forward of the
bootstrap observation (no backward: its value is a constant). The
backward of a layer costs its input gradient and its weight gradient,
each as much as its forward, except that no gradient flows into the
observation.
"""
from __future__ import annotations

from typing import List, Tuple


def cnn_layers(obs_shape, arch, n_actions) -> List[Tuple[str, float]]:
    """(name, forward FLOPs for one observation) per layer."""
    h, w, cin = obs_shape
    out = []
    for i, (f, k, s) in enumerate(zip(arch["conv_filters"],
                                      arch["conv_sizes"],
                                      arch["conv_strides"])):
        h, w = (h - k) // s + 1, (w - k) // s + 1
        out.append((f"conv{i}", 2.0 * h * w * k * k * cin * f))
        cin = f
    flat = h * w * cin
    out.append(("fc", 2.0 * flat * arch["hidden"]))
    out.append(("heads", 2.0 * arch["hidden"] * (n_actions + 1)))
    return out


def layers(policy: dict, obs_shape, n_actions):
    if policy["name"] == "cnn":
        return cnn_layers(obs_shape, policy["arch"], n_actions)
    raise ValueError(f"no FLOP count for policy {policy['name']!r}")


def forward(policy, obs_shape, n_actions) -> float:
    return sum(f for _, f in layers(policy, obs_shape, n_actions))


def backward(policy, obs_shape, n_actions) -> float:
    """Weight gradients of every layer, input gradients of all but the
    first."""
    ls = layers(policy, obs_shape, n_actions)
    return 2 * sum(f for _, f in ls) - ls[0][1]


def train_step(policy, obs_shape, n_actions, alpha: int) -> float:
    """Model FLOPs per env step trained."""
    fwd = forward(policy, obs_shape, n_actions)
    return fwd + fwd + backward(policy, obs_shape, n_actions) + fwd / alpha

