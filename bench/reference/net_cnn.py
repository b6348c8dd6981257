"""The paper's conv policy (HTS-RL appendix F: conv 32/64/64, fc 512,
policy and value heads), written plainly, and its model FLOPs by layer.
ReLU's derivative at 0 is 0, as in the frameworks the paper used."""
from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from bench.reference import nets


def cnn_init(key, obs_shape, n_actions, filters, sizes, strides, hidden):
    """He-normal conv and fc weights, zero biases, a 0.01-scaled policy
    head and a unit-scaled value head."""
    ks = jax.random.split(key, len(filters) + 3)
    h, w, cin = obs_shape
    params = {}
    for i, (f, k, s) in enumerate(zip(filters, sizes, strides)):
        params[f"conv{i}_w"] = (jax.random.normal(ks[i], (k, k, cin, f))
                                * math.sqrt(2.0 / (k * k * cin)))
        params[f"conv{i}_b"] = jnp.zeros((f,))
        h, w, cin = (h - k) // s + 1, (w - k) // s + 1, f
    flat = h * w * cin
    params["fc_w"] = (jax.random.normal(ks[-3], (flat, hidden))
                      * math.sqrt(2.0 / flat))
    params["fc_b"] = jnp.zeros((hidden,))
    params["pi_w"] = jax.random.normal(ks[-2], (hidden, n_actions)) * 0.01
    params["pi_b"] = jnp.zeros((n_actions,))
    params["v_w"] = jax.random.normal(ks[-1], (hidden, 1))
    params["v_b"] = jnp.zeros((1,))
    return params


def cnn_apply(params, obs, strides, mode="highest"):
    """obs (B, H, W, C) -> (logits (B, A), value (B,)), in ``mode``."""
    dtype = nets.arith(mode)
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    x = obs.astype(dtype)
    for i, s in enumerate(strides):
        x = jax.nn.relu(nets.conv(x, p[f"conv{i}_w"], s, mode)
                        + p[f"conv{i}_b"])
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(nets.dot(x, p["fc_w"], mode) + p["fc_b"])
    logits = nets.dot(x, p["pi_w"], mode) + p["pi_b"]
    value = (nets.dot(x, p["v_w"], mode) + p["v_b"])[:, 0]
    return logits, value


def make(policy: dict, obs_shape, n_actions):
    """(init(key), apply(params, obs, mode)) for a ``cnn`` policy block."""
    a = policy["arch"]
    init = lambda key: cnn_init(key, tuple(obs_shape), n_actions,
                                a["conv_filters"], a["conv_sizes"],
                                a["conv_strides"], a["hidden"])
    apply = lambda p, obs, mode="highest": cnn_apply(
        p, obs, a["conv_strides"], mode)
    return init, apply


def layers(obs_shape, arch, n_actions) -> List[Tuple[str, float]]:
    """(name, forward FLOPs for one observation) per layer: the
    multiply-adds of the convolutions, the fc layer and the heads."""
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
