"""The policies, written plainly, and the arithmetic they share.

Each policy's reference is a file of its own, ``net_<name>.py`` beside
this one: ``make(policy, obs_shape, n_actions) -> (init, apply)`` and
``layers(obs_shape, arch, n_actions)``, its model FLOPs by layer
(``bench.flops``); ``make`` and ``load`` here find it by the policy's
name. ``apply(params, obs, mode)`` computes in ``mode``.

``mode`` names the arithmetic (``MODES``): ``highest`` is float32 at
``Precision.HIGHEST``, the reference; ``high`` is float32 at three
bfloat16 passes, ``one_pass`` at one (what a TPU does at its default
precision, written out so that it reads the same on every platform),
and ``default`` at the platform's default precision; ``bfloat16`` keeps
every array, weights included, in bfloat16. The last four serve as
lower-precision controls. Parameter names follow the layout the system
under test takes, so the benchmark can hand it the weights made here.
"""
from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp

from bench.harness import BENCH_DIR, load_file

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


def dot(x, w, mode):
    """``x @ w`` in ``mode``."""
    if mode in ("default", "bfloat16"):
        return jnp.dot(x, w)
    return _product(_dot_exact, x, w, mode)


def conv(x, w, stride, mode):
    """A VALID NHWC convolution in ``mode``."""
    if mode in ("default", "bfloat16"):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return _product(_conv_exact(stride), x, w, mode)


def load(name: str, bench_dir: Path = BENCH_DIR):
    """The module of policy ``name``: ``reference/net_<name>.py`` under
    ``bench_dir``."""
    return load_file("reference", name, bench_dir, prefix="net_")


def make(policy: dict, obs_shape, n_actions, bench_dir: Path = BENCH_DIR):
    """(init(key), apply(params, obs, mode)) for a configuration's
    ``policy`` block (its ``name`` and ``arch``)."""
    return load(policy["name"], bench_dir).make(policy, obs_shape,
                                                n_actions)
