"""Environment registry — the third leaf registry (after runtimes and
algorithms): ``get_env(name, **kwargs)`` resolves a *workload source* by
name, so experiment specs (repro.api.ExperimentSpec) can name their
environment instead of importing a factory.

Most entries build an ``Env`` (repro.envs.interfaces): a bundle of pure
``reset``/``step`` functions that every engine runtime replicates to
``cfg.n_envs``. One entry — ``token_stream`` — builds a
``repro.data.pipeline.TokenStream`` instead: the batched deterministic
token source consumed ONLY by the ``stream`` runtime (the LLM-scale
learner loop behind ``repro.launch.train``). ``repro.api.build``
enforces that pairing; the registry itself just constructs.

    from repro import envs
    env1 = envs.get_env("catch")
    envs.env_names()   # -> ['catch', 'football', 'gridmaze', 'token', ...]

Built-ins resolve lazily (importing this package never drags in every
environment module); third parties add entries with ``@register_env``.
"""
from __future__ import annotations

import importlib
from typing import Any, Callable, Dict

_REGISTRY: Dict[str, Callable[..., Any]] = {}

# name -> (module, factory attribute), imported on first lookup
_LAZY: Dict[str, tuple] = {
    "catch": ("repro.envs.catch", "make"),
    "gridmaze": ("repro.envs.gridmaze", "make"),
    "gridmaze84": ("repro.envs.gridmaze84", "make"),
    "football": ("repro.envs.football", "make"),
    "token": ("repro.envs.token_env", "make"),
    "token_stream": ("repro.data.pipeline", "TokenStream"),
    # device-resident batched ports (repro.envs.device): registered
    # alongside their host oracles. Specs normally reach them through
    # ``hts.env_backend="device"`` with the HOST name; the ``_device``
    # entries exist for direct construction and tests.
    "catch_device": ("repro.envs.device.catch", "make"),
    "gridmaze_device": ("repro.envs.device.gridmaze", "make"),
    "gridmaze84_device": ("repro.envs.device.gridmaze84", "make"),
}


def register_env(name: str):
    """Factory decorator: ``@register_env("my_env")`` over a
    ``(**kwargs) -> Env`` callable."""
    def deco(factory):
        _REGISTRY[name] = factory
        return factory
    return deco


def get_env_factory(name: str) -> Callable[..., Any]:
    """Resolve an environment factory by registry name."""
    if name not in _REGISTRY and name in _LAZY:
        module, attr = _LAZY[name]
        _REGISTRY[name] = getattr(importlib.import_module(module), attr)
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown env {name!r}; "
                       f"registered: {env_names()}") from None


def get_env(name: str, **kwargs):
    """Construct a registered environment: ``get_env("catch")``,
    ``get_env("token", vocab=128)``."""
    return get_env_factory(name)(**kwargs)


def env_names():
    return sorted(set(_REGISTRY) | set(_LAZY))


def has_device_port(name: str) -> bool:
    """Does host env ``name`` have a device-resident port
    (``HTSConfig.env_backend="device"``)? See repro.envs.device."""
    from repro.envs import device as device_envs
    return device_envs.has_device_port(name)


def get_device_env(name: str, **kwargs):
    """Construct the device-resident port of host env ``name``; loud
    ValueError listing the supported pairs when there is none."""
    from repro.envs import device as device_envs
    return device_envs.get_device_env(name, **kwargs)
