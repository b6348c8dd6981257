"""Pallas TPU kernel for the RWKV-6 WKV recurrence.

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

The recurrence is inherently sequential in t (this is also true of the
official CUDA kernel); parallelism comes from (B, H). Grid
(B, H, T//chunk) with chunks as the fastest (sequential) axis; the
(N, N) state lives in VMEM scratch across chunk steps.

The wrapper puts the head axis ahead of time — (B, T, H, N) ->
(B, H, T, N) — and carries u as (H, 1, N), so every block's last two
dims are (chunk, N) or (1, N): divisible by 8 or equal to the array's,
as the TPU compiler's tiling rule requires. Rows are read and written
one (1, N) slice at a time from the refs.

BlockSpecs: r/k/v/w tiles (1, 1, chunk, N); u tile (1, 1, N); o tile
like r; s0/s_T tiles (1, 1, N, N).
VMEM = 4 * chunk * N * 4B + N^2 * 4B   (chunk=128, N=64 -> 148 KB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_out_ref,
            s_ref, *, chunk, n_chunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    u = u_ref[0].T                              # (N, 1)

    def body(t, s):
        rows = pl.ds(t, 1)
        row = lambda ref: ref[0, 0, rows]       # (1, N)
        r, k, v, w = row(r_ref), row(k_ref), row(v_ref), row(w_ref)
        kv = k.T * v                            # (N, N)
        o = (r.T * (s + u * kv)).sum(axis=0, keepdims=True)
        o_ref[0, 0, rows] = o
        return w.T * s + kv

    s = jax.lax.fori_loop(0, chunk, body, s_ref[...])
    s_ref[...] = s

    @pl.when(ci == n_chunks - 1)
    def _final():
        s_out_ref[0, 0] = s_ref[...]


def wkv6(r, k, v, w, u, s0=None, *, chunk: int = 128,
         interpret: bool = True):
    """r,k,v,w: (B, T, H, N); u: (H, N); s0: (B, H, N, N) f32 or None.

    Returns (o (B,T,H,N), s_T (B,H,N,N) f32)."""
    B, T, H, N = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0
    if s0 is None:
        s0 = jnp.zeros((B, H, N, N), jnp.float32)
    grid = (B, H, T // chunk)
    # the kernel computes in f32 anyway; converting in the (fused)
    # transpose keeps its one-row loads and stores on unpacked f32 tiles,
    # whose dynamic row offsets the compiler accepts for any dtype
    heads_first = lambda x: jnp.swapaxes(x, 1, 2).astype(jnp.float32)
    row = pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0))

    o, sT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=T // chunk),
        grid=grid,
        in_specs=[
            row, row, row, row,
            pl.BlockSpec((1, 1, N), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            row,
            pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(heads_first(r), heads_first(k), heads_first(v), heads_first(w),
      u.reshape(H, 1, N).astype(jnp.float32), s0.astype(jnp.float32))
    return jnp.swapaxes(o, 1, 2).astype(r.dtype), sT
