"""Pallas TPU kernel for the RG-LRU linear recurrence.

TPU-native re-think of a GPU scan: instead of a two-pass Blelchoch scan
with inter-block carries in global memory, we exploit the TPU grid's
SEQUENTIAL execution order — grid (B, D//bd, S//chunk) with the sequence
chunks as the fastest axis. The running state h for one (b, d-block) lives
in VMEM scratch across chunk steps; within a chunk the recurrence is an
unrolled-by-8 fori loop over rows already resident in VMEM.

BlockSpecs: a, b, y tiles (1, chunk, bd); h0 tile (1, 1, bd) over h0
carried as (B, 1, D), so the last two block dims are (1 = full, bd) and
the TPU compiler's (8, 128) tiling rule holds for any batch size. Rows
are read and written one (1, bd) slice at a time from the refs, on f32
tiles: the wrapper converts a and b (the kernel computes in f32 anyway),
because the compiler accepts a dynamic one-row offset only into unpacked
32-bit tiles.
VMEM footprint = 3 * chunk * bd * 4B + bd * 4B  (chunk=256, bd=512 -> 1.5 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, y_ref, h_ref, *, chunk):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0]                              # (1, bd)

    # Mosaic lowers fori_loop only fully unrolled or not at all, so the
    # unroll-by-8 is written out: each loop step advances `unroll` rows
    unroll = 8 if chunk % 8 == 0 else 1

    def body(i, h):
        for u in range(unroll):
            rows = pl.ds(i * unroll + u, 1)
            h = a_ref[0, rows] * h + b_ref[0, rows]
            y_ref[0, rows] = h
        return h

    h = jax.lax.fori_loop(0, chunk // unroll, body, h_ref[...])
    h_ref[...] = h


def lru_scan(a, b, h0=None, *, chunk: int = 256, bd: int = 512,
             interpret: bool = True):
    """a, b: (B, S, D); h0: (B, D) or None -> (h (B,S,D), h_last (B,D))."""
    B, S, D = a.shape
    chunk = min(chunk, S)
    bd = min(bd, D)
    assert S % chunk == 0 and D % bd == 0
    h0 = (jnp.zeros((B, 1, D), jnp.float32) if h0 is None
          else h0.reshape(B, 1, D).astype(jnp.float32))
    grid = (B, D // bd, S // chunk)

    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, chunk, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, 1, bd), lambda bi, di, si: (bi, 0, di)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bd),
                               lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32), h0)
    y = y.astype(a.dtype)
    return y, y[:, -1].astype(jnp.float32)
