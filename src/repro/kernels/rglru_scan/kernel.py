"""Blocked RG-LRU linear scan — Pallas TPU kernel.

XLA's ``associative_scan`` lowers to O(log T) full passes over HBM
(~2 log2(T) reads/writes of the (B,T,W) tensor).  This kernel makes exactly
ONE pass: grid (B, W/BW, T/C) with time innermost, the running state held in
VMEM scratch across chunks, and the C-step recurrence unrolled on the VPU
over (1, BW) lanes.  For prefill_32k at W=4096 that is a ~2x log2(32768)/2
= ~7.5x cut in scan HBM traffic (the memory-roofline term).

Tile choice: BW=512 lanes x C=128 steps = 256 KiB fp32 per operand tile —
two operands + output + state well under VMEM, leaving double-buffer room.
Time is read and written ROWS steps at a time, because a dynamic offset
along the sublane axis must be a whole tile; h0/hT travel as (B, 1, W) so
their blocks' last two dims (1, BW) meet the (8, 128) rule for any B.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BW = 512
DEFAULT_CHUNK = 128
ROWS = 16          # time steps per aligned tile (bf16 packs 16 sublanes)


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, hT_ref, h_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    def rows(i, h):
        # one ROWS x BW tile per load/store (a dynamic sublane offset must be
        # tile aligned); the ROWS steps inside it are unrolled on values
        t = pl.multiple_of(i * ROWS, ROWS)
        a = a_ref[0, pl.ds(t, ROWS)].astype(jnp.float32)      # (ROWS, BW)
        b = b_ref[0, pl.ds(t, ROWS)].astype(jnp.float32)
        hs = []
        for j in range(ROWS):
            h = a[j:j + 1] * h + b[j:j + 1]                    # (1, BW)
            hs.append(h)
        o_ref[0, pl.ds(t, ROWS)] = jnp.concatenate(hs).astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // ROWS, rows, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == nc - 1)
    def _emit():
        hT_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("bw", "chunk", "interpret"))
def rglru_scan_fwd(a: jax.Array, b: jax.Array, h0: jax.Array, *,
                   bw: int = DEFAULT_BW, chunk: int = DEFAULT_CHUNK,
                   interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """a/b: (B,T,W); h0: (B,W). Returns (h (B,T,W), hT (B,W) fp32).

    T is either a multiple of ``chunk`` or shorter than it; a T that is not
    a multiple of ROWS is padded with identity steps (a=1, b=0), which
    leave the state unchanged, and the padding is cut from ``h``."""
    B, T, W = a.shape
    Tp = -(-T // ROWS) * ROWS
    if Tp != T:
        pad = ((0, 0), (0, Tp - T), (0, 0))
        a = jnp.pad(a, pad, constant_values=1)
        b = jnp.pad(b, pad)
    BW = min(bw, W)
    C = min(chunk, Tp)
    assert W % BW == 0 and Tp % C == 0 and C % ROWS == 0, (
        f"W={W} BW={BW} T={Tp} chunk={C} ROWS={ROWS}")

    grid = (B, W // BW, Tp // C)
    kernel = functools.partial(_rglru_kernel, chunk=C)
    out, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, BW), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, C, BW), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, 1, BW), lambda bi, wi, ci: (bi, 0, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, BW), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, 1, BW), lambda bi, wi, ci: (bi, 0, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, W), a.dtype),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, BW), jnp.float32)],
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, W))
    return out[:, :T], hT.reshape(B, W)
