"""jit'd, differentiable public wrapper for the flash attention kernel.

Accepts the model's (B, S, H, Dh) layout, transposes to the kernel's
(B, H, S, Dh), pads the sequence to a block multiple, and dispatches to
the Pallas kernel (interpret mode on CPU) or the jnp oracle.

Gradients: the Pallas forward carries a ``jax.custom_vjp`` whose
backward differentiates the jnp oracle on the saved (q, k, v) — the
standard fused-forward/XLA-backward split (the O(S^2) recompute happens
only under ``grad``; inference never pays it). A dedicated backward
kernel is a future optimization; the contract that matters — identical
gradients on the Pallas and oracle paths — is what
tests/test_kernels.py pins down.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _attend_pallas(qt, kt, vt, causal, window, cap, kv_len, bq, bk,
                   interpret):
    return flash_attention(qt, kt, vt, causal=causal, window=window,
                           cap=cap, kv_len=kv_len, bq=bq, bk=bk,
                           interpret=interpret)


def _attend_fwd(qt, kt, vt, causal, window, cap, kv_len, bq, bk,
                interpret):
    out = _attend_pallas(qt, kt, vt, causal, window, cap, kv_len, bq, bk,
                         interpret)
    return out, (qt, kt, vt)


def _attend_bwd(causal, window, cap, kv_len, bq, bk, interpret, res, g):
    qt, kt, vt = res
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention_ref(q, k, v, causal=causal,
                                            window=window, cap=cap,
                                            kv_len=kv_len),
        qt, kt, vt)
    return vjp(g)


_attend_pallas.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "bq", "bk", "use_pallas",
                                             "interpret"))
def attend(q, k, v, *, causal: bool = True, window: int = 0,
           cap: float = 0.0, bq: int = 128, bk: int = 128,
           use_pallas: bool | None = None, interpret: bool | None = None):
    """q: (B, S, H, Dh); k, v: (B, S, KV, Dh) -> (B, S, H, Dh).

    use_pallas/interpret default to auto-routing per backend: compiled
    Pallas on TPU, interpreted Pallas elsewhere (repro.kernels). Both
    paths are differentiable (see module docstring)."""
    from repro.kernels import resolve_backend
    use_pallas, interpret = resolve_backend(use_pallas, interpret)
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    bq_ = min(bq, Sq)
    bk_ = min(bk, Sk)
    pq = (-Sq) % bq_
    pk = (-Sk) % bk_
    kv_len = Sk if pk else None
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    if use_pallas:
        ot = _attend_pallas(qt, kt, vt, causal, window, cap, kv_len,
                            bq_, bk_, interpret)
    else:
        ot = flash_attention_ref(qt, kt, vt, causal=causal, window=window,
                                 cap=cap, kv_len=kv_len)
    return jnp.transpose(ot[:, :, :Sq], (0, 2, 1, 3))
