"""Device-resident environment fleet: natively-batched pure-JAX ports.

A ``DeviceEnv`` is the batched sibling of ``repro.envs.interfaces.Env``:
its ``reset``/``step`` operate directly on STACKED per-env state pytrees
(every leaf carries a leading ``n_envs`` axis) instead of being a scalar
program replicated by ``jax.vmap``. The call signature is deliberately
identical to ``interfaces.vectorize(env, n)``:

    reset(keys)                  -> (state, obs)         keys: (n,)
    step(state, actions, keys)   -> (state, obs, r, done)

so the fused runtimes' scan body (core/rollout.rollout_interval) and the
host runtime's batched stepper consume either interchangeably — the
``HTSConfig.env_backend`` axis selects which (``batched_env``, below).

Why a hand-batched port when vmap already traces to one program: the
vmapped envs materialize observations through per-row scatters
(``board.at[r, c].set(1.0)`` under vmap lowers to batched
scatter/dynamic-update ops), which are the slowest lane on TPU-class
backends; the device ports build the same boards from broadcast
comparisons and elementwise products — VPU-shaped code with no scatter
on the hot path. PRNG draws, where an env has them, still go through
``jax.vmap`` of the exact per-key op the host env performs: that is what
makes the port *bit-exact*, not merely equivalent.

The oracle contract (DESIGN.md §2.2, tests/test_device_envs.py): for
every registered port, ``vectorize(host_env, n)`` and the DeviceEnv
produce bit-identical (state, obs, reward, done) streams for identical
(keys, actions) inputs — including through auto-reset boundaries. The
host envs stay the semantic source of truth; a port that drifts fails
the equivalence suite, not a downstream golden.

Registry: ports register against the HOST env's registry name
(``@register_device_port("catch")``); ``has_device_port``/
``get_device_env`` resolve them, and ``repro.envs.get_env`` also exposes
each port as ``"<name>_device"`` alongside the host version.
"""
from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.envs.interfaces import Env, _bcast, vectorize


class DeviceEnv(NamedTuple):
    """A natively-batched jittable env over stacked per-env state.

    Field-compatible with ``interfaces.Env`` (same attribute names) so
    every consumer of a vectorized Env — rollout scan bodies, policy
    sizing, the host runtime's batched stepper — duck-types over both.
    ``host_name`` records which host env this is the device port of
    (the oracle the equivalence suite compares against).
    """
    name: str
    reset: Callable          # keys (n,) -> (state, obs (n, ...))
    step: Callable           # (state, actions (n,), keys (n,)) -> 4-tuple
    obs_shape: Tuple[int, ...]
    n_actions: int
    host_name: str


def device_autoreset(name, reset_fn, inner_step, obs_shape, n_actions,
                     host_name) -> DeviceEnv:
    """Batched mirror of ``interfaces.with_autoreset``: on done rows the
    returned state/obs are already the first of the next episode. The
    reset key is ``fold_in(key, 7)`` per row — the SAME derivation the
    host wrapper applies per scalar env, so the PRNG stream (and hence
    every downstream value) is bit-identical to the vmapped host env."""

    fold7 = jax.vmap(lambda k: jax.random.fold_in(k, 7))

    def step(state, actions, keys):
        ns, obs, r, done = inner_step(state, actions, keys)
        rs, robs = reset_fn(fold7(keys))
        state_out = jax.tree.map(
            lambda a, b: jnp.where(_bcast(done, a), b, a), ns, rs)
        obs_out = jnp.where(_bcast(done, obs), robs, obs)
        return state_out, obs_out, r, done

    return DeviceEnv(name, reset_fn, step, obs_shape, n_actions, host_name)


# ------------------------------------------------------------- registry
_REGISTRY: Dict[str, Callable[..., DeviceEnv]] = {}

# host env name -> (module, factory attribute), imported on first lookup
_LAZY: Dict[str, tuple] = {
    "catch": ("repro.envs.device.catch", "make"),
    "gridmaze": ("repro.envs.device.gridmaze", "make"),
    "gridmaze84": ("repro.envs.device.gridmaze84", "make"),
}


def register_device_port(host_name: str):
    """Factory decorator: ``@register_device_port("my_env")`` over a
    ``(**kwargs) -> DeviceEnv`` callable, keyed by the HOST env's
    registry name (the oracle it ports)."""
    def deco(factory):
        _REGISTRY[host_name] = factory
        return factory
    return deco


def has_device_port(host_name: str) -> bool:
    return host_name in _REGISTRY or host_name in _LAZY


def device_port_names() -> list:
    """Host env names that have a device-resident port."""
    return sorted(set(_REGISTRY) | set(_LAZY))


def get_device_env(host_name: str, **kwargs) -> DeviceEnv:
    """Resolve and construct the device port of a host env by the host
    env's registry name. Loud on envs with no port — the supported
    pairs are listed so the fix is obvious."""
    if host_name not in _REGISTRY and host_name in _LAZY:
        module, attr = _LAZY[host_name]
        _REGISTRY[host_name] = getattr(importlib.import_module(module), attr)
    try:
        factory = _REGISTRY[host_name]
    except KeyError:
        raise ValueError(
            f"env {host_name!r} has no device-resident port; "
            f"env_backend='device' supports {device_port_names()} "
            f"(use env_backend='host' for the rest)") from None
    return factory(**kwargs)


def batched_env(env: Env, n_envs: int, backend: str = "host"):
    """The one place ``HTSConfig.env_backend`` is interpreted: resolve
    the batched env every runtime steps ``n_envs`` replicas through.

    ``"host"``   -> ``vectorize(env, n_envs)`` (vmapped scalar env —
                    today's semantics, and the bit-exactness oracle);
    ``"device"`` -> the env's registered DeviceEnv port (natively
                    batched, stepped inside the scan body with no host
                    dispatch). Unknown backends and envs without a port
                    fail HERE, at runtime construction — never at trace
                    time."""
    if backend == "host":
        return vectorize(env, n_envs)
    if backend == "device":
        # the host env's construction kwargs (a scenario seed, say)
        # travel with it — the port must be built the same way, or two
        # backends of one spec would quietly step different worlds
        return get_device_env(env.name,
                              **(getattr(env, "make_kwargs", None) or {}))
    raise ValueError(
        f"unknown env_backend {backend!r}; choose 'host' (vmapped "
        f"scalar envs) or 'device' (device-resident batched port)")


def make_device_env(host_name: str, **kwargs) -> DeviceEnv:
    """`repro.envs.get_env("<name>_device")` entry point."""
    return get_device_env(host_name, **kwargs)
