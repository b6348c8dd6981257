"""Pallas kernels (flash attention, LRU scan, WKV6) with CPU fallbacks.

Each kernel package exposes three layers:

  kernel.py  — the Pallas implementation (TPU-shaped grids/blocks);
  ref.py     — a pure-jnp oracle, used for testing and as a fallback;
  ops.py     — the jit'd public wrapper that auto-routes per backend.

Routing: on TPU the Pallas kernel runs compiled; anywhere else it runs in
interpret mode (faithful to the kernel's arithmetic, slow, and blind to
what the TPU compiler refuses — tests/test_tpu_compile.py covers that),
or callers can force the jnp oracle by turning ``use_pallas`` off.
``resolve_backend`` centralizes that decision so the three wrappers stay
in sync.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(use_pallas, interpret):
    """Fill in auto (None) routing flags: (use_pallas, interpret)."""
    if use_pallas is None:
        use_pallas = True
    if interpret is None:
        interpret = not on_tpu()
    return use_pallas, interpret
