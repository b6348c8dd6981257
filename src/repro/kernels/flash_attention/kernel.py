"""Pallas TPU flash attention (causal / sliding-window / softcap, GQA).

Grid: (B, H, nq, nk) — the TPU grid is executed sequentially with the last
dimension fastest, so the online-softmax state for one (b, h, qi) lives in
VMEM scratch across the nk steps and is finalized on the last one.

BlockSpecs (VMEM tiles):
  q:   (1, 1, Bq, Dh)   index (b, h, qi)          — Bq x Dh tile
  k,v: (1, 1, Bk, Dh)   index (b, h // R, ki)     — GQA: kv head shared
  out: (1, 1, Bq, Dh)

Default Bq=Bk=128 and Dh in {64,128,256}: the qk^T tile is 128x128 (MXU
native), VMEM footprint ~ (Bq*Dh + 2*Bk*Dh + Bq*Bk) * 4B  < 1 MB.

Targets TPU; validated on CPU in interpret mode against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal, window, cap, scale, kv_len, nk, bq, bk):
    b, h, qi, ki = (pl.program_id(i) for i in range(4))

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk

    # skip fully-masked tiles (grid still iterates; compute is gated)
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + bq - 1)
    if window:
        run = run & (k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (Bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)          # (Bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (Bq, Bk)
        if cap:
            s = cap * jnp.tanh(s / cap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        if kv_len is not None:
            mask &= kpos < kv_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    cap: float = 0.0, kv_len=None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = True):
    """q: (B, H, Sq, Dh); k, v: (B, KV, Sk, Dh). Returns (B, H, Sq, Dh)."""
    B, H, Sq, Dh = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    R = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, "pad sequence to block multiple"
    nq, nk = Sq // bq, Sk // bk
    grid = (B, H, nq, nk)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, cap=cap,
        scale=Dh ** -0.5, kv_len=kv_len, nk=nk, bq=bq, bk=bk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, qi, ki, R=R: (b, h // R, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, qi, ki, R=R: (b, h // R, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((bq, 1), jnp.float32),   # l (running denom)
            pltpu.VMEM((bq, Dh), jnp.float32),  # acc (weighted values)
        ],
        interpret=interpret,
    )(q, k, v)
