"""The policy, written plainly: the paper's conv trunk (appendix F:
conv 32/64/64, fc 512, policy and value heads).

``mode`` names the arithmetic (``MODES``): ``highest`` is float32 at
``Precision.HIGHEST``, the reference; ``high`` is float32 at three
bfloat16 passes, ``one_pass`` at one (what a TPU does at its default
precision, written out so that it reads the same on every platform),
and ``default`` at the platform's default precision; ``bfloat16`` keeps
every array, weights included, in bfloat16. The last four serve as
lower-precision controls. ReLU's derivative at 0 is 0,
as in the frameworks the paper used. Parameter names follow the
layout the system under test takes, so the benchmark can hand it the
weights it makes here.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

P = jax.lax.Precision
BF16, F32 = jnp.bfloat16, jnp.float32
MODES = {"highest": F32, "high": F32, "one_pass": F32, "default": F32,
         "bfloat16": BF16}


def arith(mode: str):
    """The array dtype of an arithmetic mode."""
    try:
        return MODES[mode]
    except KeyError:
        raise ValueError(f"unknown arithmetic {mode!r}; have "
                         f"{sorted(MODES)}") from None


def _split(x):
    """x = hi + lo, both rounded to bfloat16's 8 significant bits and
    held in float32. ``reduce_precision`` is an op the compiler must
    keep; a bfloat16 round trip by ``astype`` it may drop as excess
    precision, which leaves lo at 0."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi, jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                        mantissa_bits=7)


def _three(op, a, b):
    """``op(a, b)`` as three bfloat16 products accumulated in float32:
    hi*hi + hi*lo + lo*hi (what ``Precision.HIGH`` does on a TPU)."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def _one(op, a, b):
    """``op(a, b)`` as one bfloat16 product accumulated in float32."""
    return op(_split(a)[0], _split(b)[0])


def _passes(product):
    """A dot or convolution ``op(a, b)`` whose forward and backward
    products are all computed by ``product``."""
    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def f(op, a, b):
        return product(op, a, b)

    def fwd(op, a, b):
        return product(op, a, b), (a, b)

    def bwd(op, res, g):
        a, b = res
        da = product(
            lambda gg, bb: jax.vjp(lambda x: op(x, bb), a)[1](gg)[0], g, b)
        db = product(
            lambda aa, gg: jax.vjp(lambda y: op(aa, y), b)[1](gg)[0], a, g)
        return da, db

    f.defvjp(fwd, bwd)
    return f


_PASSES = {"high": _passes(_three), "one_pass": _passes(_one)}


def _dot_exact(a, b):
    return jnp.dot(a, b, precision=P.HIGHEST)


@functools.lru_cache(maxsize=None)
def _conv_exact(stride):
    return lambda a, b: jax.lax.conv_general_dilated(
        a, b, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=P.HIGHEST)


def _product(exact, x, w, mode):
    """A dot or convolution in ``mode``. ``high`` and ``one_pass`` are
    written out as bfloat16 products, forward and backward, so the
    controls read the same on every platform."""
    if mode in _PASSES:
        return _PASSES[mode](exact, x, w)
    if mode == "highest":
        return exact(x, w)
    raise ValueError(f"no exact product in mode {mode!r}")


def _dot(x, w, mode):
    if mode in ("default", "bfloat16"):
        return jnp.dot(x, w)
    return _product(_dot_exact, x, w, mode)


def _conv(x, w, stride, mode):
    if mode in ("default", "bfloat16"):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return _product(_conv_exact(stride), x, w, mode)


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
    dtype = arith(mode)
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    x = obs.astype(dtype)
    for i, s in enumerate(strides):
        x = jax.nn.relu(_conv(x, p[f"conv{i}_w"], s, mode)
                        + p[f"conv{i}_b"])
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(_dot(x, p["fc_w"], mode) + p["fc_b"])
    logits = _dot(x, p["pi_w"], mode) + p["pi_b"]
    value = (_dot(x, p["v_w"], mode) + p["v_b"])[:, 0]
    return logits, value


def make(policy: dict, obs_shape, n_actions):
    """(init(key), apply(params, obs, mode)) for a configuration's
    ``policy`` block."""
    if policy["name"] == "cnn":
        a = policy["arch"]
        init = lambda key: cnn_init(key, tuple(obs_shape), n_actions,
                                    a["conv_filters"], a["conv_sizes"],
                                    a["conv_strides"], a["hidden"])
        apply = lambda p, obs, mode="highest": cnn_apply(
            p, obs, a["conv_strides"], mode)
        return init, apply
    raise ValueError(f"no reference network for policy {policy['name']!r}")
