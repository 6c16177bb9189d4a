"""Chunked WKV6 — Pallas TPU kernel.

The WKV6 recurrence is a gated linear attention: a naive step-by-step scan
does T sequential (D x D) state updates with no MXU utilization.  The chunked
form processes C tokens at once with dense matmuls (TPU-native adaptation of
the paper family's CUDA kernels):

  within a chunk, with cumulative per-channel log-decay L_t = sum_{j<=t} log w_j:
    out_t = (r_t * exp(L_{t-1})) @ S0                          (state term, MXU)
          + sum_{s<t} [sum_d r_td k_sd exp(L_{t-1}-L_s)] v_s   (intra, pairwise)
          + (r_t * u * k_t) @ v_t                              (diagonal bonus)
    S_next = diag(exp(L_C)) S0 + sum_s (exp(L_C - L_s) * k_s)^T v_s

  Every exponent is <= 0 (decays are < 1), so exp() never overflows and
  underflow saturates harmlessly at 0 — numerically stable without the
  1/decay rescaling trick GPU kernels use.

Tiling: grid (B, H, T/C), chunk dim innermost/sequential, the (D x D) fp32
state carried in VMEM scratch.  At C=64, D=64: pairwise tensor (C,C,D) fp32
= 1 MiB, state 16 KiB, tiles 4x16 KiB — comfortably inside VMEM.  The
kernel sees head-major (B, H, T, D) operands so every block's last two dims
are a (C, D) tile that meets the TPU's (8, 128) tiling rule (D equals the
full dim); the (B, T, H, D) public layout is transposed at the call.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _wkv6_kernel(r_ref, k_ref, v_ref, logw_ref, u_ref, s0_ref,
                 o_ref, sT_ref, state_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)
    C = chunk

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)               # (C, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    logw = logw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                  # (1, D)

    # inclusive prefix sum over time as a lower-triangular matmul (Mosaic
    # has no cumsum): L = tril(1) @ logw
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    L = jax.lax.dot_general((rows >= cols).astype(jnp.float32), logw,
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (C, D) <= 0
    Lprev = L - logw                                  # L_{t-1} (zero at t=0)

    S0 = state_ref[...]                               # (D, Dv)
    # ---- state term: (r_t * exp(L_{t-1})) @ S0
    r_dec = r * jnp.exp(Lprev)
    out = jax.lax.dot_general(r_dec, S0, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)

    # ---- intra-chunk pairwise term (strictly causal s < t)
    # P[t,s] = sum_d r_td k_sd exp(Lprev_t - L_s)_d  (exponent <= 0 for s < t)
    diff = Lprev[:, None, :] - L[None, :, :]          # (C, C, D)
    causal = (jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
              > jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1))
    pair = jnp.where(causal, jnp.exp(diff), 0.0)
    P = jnp.sum(r[:, None, :] * k[None, :, :] * pair, axis=-1)   # (C, C)
    out = out + jax.lax.dot_general(P, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    # ---- diagonal bonus: (r_t * u * k_t) . v_t
    out = out + jnp.sum(r * u * k, axis=1, keepdims=True) * v
    o_ref[0, 0] = out.astype(o_ref.dtype)

    # ---- state update: S_next = diag(exp(L_C)) S0 + (exp(L_C - L) * k)^T v
    L_C = jnp.sum(logw, axis=0, keepdims=True)        # (1, D) = L[-1]
    k_dec = k * jnp.exp(L_C - L)                      # (C, D)
    state_ref[...] = jnp.exp(L_C).T * S0 + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _emit_state():
        sT_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_fwd(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
             u: jax.Array, state: jax.Array, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """r/k/v/w: (B,T,H,D); u: (H,D); state: (B,H,D,D) fp32.
    Returns (out (B,T,H,D), final state (B,H,D,D))."""
    B, T, H, D = r.shape
    C = min(chunk, T)
    assert T % C == 0, (T, C)
    logw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))
    head_major = lambda x: x.transpose(0, 2, 1, 3)    # (B,T,H,D)->(B,H,T,D)

    grid = (B, H, T // C)
    kernel = functools.partial(_wkv6_kernel, chunk=C)
    tile = lambda b, h, c: (b, h, c, 0)
    out, sT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, C, D), tile),
            pl.BlockSpec((1, 1, C, D), tile),
            pl.BlockSpec((1, 1, C, D), tile),
            pl.BlockSpec((1, 1, C, D), tile),
            pl.BlockSpec((1, 1, D), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, D), tile),
            pl.BlockSpec((1, 1, D, D), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), r.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(head_major(r), head_major(k), head_major(v), head_major(logw),
      u.reshape(H, 1, D), state.astype(jnp.float32))
    return head_major(out), sT
