"""KV-cache / recurrent-state containers for decode.

All caches are plain pytrees (dicts of arrays) with a leading layer dim that
aligns with scan-over-layers.  Shapes:

  full    : k/v (L, B, Smax, Hkv*D)
  mla     : latent (L, B, Smax, kv_lora), k_rope (L, B, Smax, dr)
  window  : k/v (L, B, W, Hkv, D) ring buffer + slot positions (L, B, W)
  rwkv    : wkv state (L, B, H, Dk, Dv) fp32 + token-shift prevs (L, B, d)
  lru     : h (L, B, lru_width) fp32 + conv tail (L, B, cw-1, lru_width)
  encdec  : decoder self cache k/v (L, B, Smax, Hkv, D) + precomputed
            cross k/v

The decoder LMs' leaves (full, mla) are (L, B, Smax, C): one position's
entries are one row of C, the minor dimension, so attention reads a
layer's (Smax, C) rows as they are stored (on a TPU, in (8, 128) tiles
with no relayout), and a decode step writes each slot's new row in place
(:func:`write_entries`) once every layer has computed it.

A model with leading dense layers (``ModelConfig.n_dense_layers``) keeps
their cache in leaves of their own, named with the ``dense_`` prefix
(``dense_latent``, ``dense_k_rope``), beside the other layers' stack: each
group's decode scan reads its own leaves, and no step concatenates them.

An expert model's cache also carries ``moe_stats`` (1, B, 2) int32: what
the last prefill or decode step's expert layers counted for each row
(``layers.moe_dropless``), so that the step's one fetch can read it.  A
step ignores the value it is given.

``long_500k`` stays feasible for ssm/hybrid because their state is O(1) in
sequence length (rwkv/lru) or bounded by the attention window (ring buffer).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig


DENSE = "dense_"      # prefix of the leading dense layers' cache leaves
LANES = 128           # a TPU vector register's minor dimension


def _maybe(shape, dtype, abstract: bool):
    if abstract:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.zeros(shape, dtype)


def _maybe_full(shape, value, dtype, abstract: bool):
    if abstract:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.full(shape, value, dtype)


# ------------------------------------------------------------------- full
def init_full_cache(cfg: ModelConfig, n_layers: int, batch: int, max_len: int,
                    abstract: bool = False) -> Dict[str, Any]:
    shape = (n_layers, batch, max_len, cfg.n_kv_heads * cfg.head_dim)
    return {"k": _maybe(shape, cfg.cdtype, abstract),
            "v": _maybe(shape, cfg.cdtype, abstract)}


def update_full_cache(ck: jax.Array, cv: jax.Array, k: jax.Array,
                      v: jax.Array, pos: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Insert one token per sequence into the encoder-decoder's self cache.
    ck/cv: (B,Smax,H,D); k/v: (B,1,H,D)."""
    b = jnp.arange(ck.shape[0])
    ck = ck.at[b, pos].set(k[:, 0].astype(ck.dtype))
    cv = cv.at[b, pos].set(v[:, 0].astype(cv.dtype))
    return ck, cv


# -------------------------------------------------------------------- MLA
def init_mla_cache(cfg: ModelConfig, n_layers: int, batch: int,
                   max_len: int, abstract: bool = False) -> Dict[str, Any]:
    L = n_layers
    return {
        "latent": _maybe((L, batch, max_len, cfg.kv_lora_rank), cfg.cdtype,
                         abstract),
        "k_rope": _maybe((L, batch, max_len, cfg.qk_rope_dim), cfg.cdtype,
                         abstract),
    }


# -------------------------------------------------- decoder LM entries
def cache_entries(cfg: ModelConfig, kv: Tuple[jax.Array, jax.Array]
                  ) -> Dict[str, jax.Array]:
    """A block's keys and values as the cache stores them, by leaf name:
    GQA's k/v (..., S, Hkv, D) as (..., S, Hkv*D); MLA's latent
    (..., S, r) as it is, and its rotary key (..., S, 1, dr) as
    (..., S, dr)."""
    a, b = kv
    if cfg.use_mla:
        return {"latent": a, "k_rope": b[..., 0, :]}
    return {"k": a.reshape(a.shape[:-2] + (-1,)),
            "v": b.reshape(b.shape[:-2] + (-1,))}


def write_entries(leaf: jax.Array, new: jax.Array, pos: jax.Array
                  ) -> jax.Array:
    """Write each slot's new entries into a stored leaf: ``leaf``
    (L, B, Smax, C), ``new`` (L, B, C) every layer's row for the token at
    ``pos`` (B,).  On a donated leaf both ways below run in place.

    Rows of a multiple of ``LANES`` entries are stored row after row, and
    one scatter of L x B rows writes them.  A TPU stores narrower rows
    (MLA's rotary key) with the positions minor, where a scatter would
    relay the whole leaf out and back; there one update per slot writes
    its L x C entries where they lie.
    """
    L, B = leaf.shape[:2]
    new = new.astype(leaf.dtype)
    if leaf.shape[-1] % LANES == 0:
        return leaf.at[jnp.arange(L)[:, None], jnp.arange(B)[None],
                       pos[None]].set(new, unique_indices=True)
    for b in range(B):
        leaf = jax.lax.dynamic_update_slice(leaf, new[:, b, None, None],
                                            (0, b, pos[b], 0))
    return leaf


# ------------------------------------------------------------------ window
def init_window_cache(cfg: ModelConfig, n_layers: int, batch: int,
                      abstract: bool = False) -> Dict[str, Any]:
    W = cfg.attn_window
    shape = (n_layers, batch, W, cfg.n_kv_heads, cfg.head_dim)
    return {"k": _maybe(shape, cfg.cdtype, abstract),
            "v": _maybe(shape, cfg.cdtype, abstract),
            "pos": _maybe_full((n_layers, batch, W), -1, jnp.int32, abstract)}


def update_window_cache(ck: jax.Array, cv: jax.Array, cpos: jax.Array,
                        k: jax.Array, v: jax.Array, pos: jax.Array
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Ring insert at slot pos % W. ck/cv: (B,W,H,D), cpos: (B,W)."""
    W = ck.shape[1]
    b = jnp.arange(ck.shape[0])
    slot = pos % W
    ck = ck.at[b, slot].set(k[:, 0].astype(ck.dtype))
    cv = cv.at[b, slot].set(v[:, 0].astype(cv.dtype))
    cpos = cpos.at[b, slot].set(pos)
    return ck, cv, cpos


# -------------------------------------------------------------------- rwkv
def init_rwkv_state(cfg: ModelConfig, batch: int,
                    abstract: bool = False) -> Dict[str, Any]:
    L, d = cfg.n_layers, cfg.d_model
    H, hs = cfg.rwkv_n_heads, cfg.rwkv_head_size
    return {
        "wkv": _maybe((L, batch, H, hs, hs), jnp.float32, abstract),
        "att_prev": _maybe((L, batch, d), cfg.cdtype, abstract),
        "ffn_prev": _maybe((L, batch, d), cfg.cdtype, abstract),
    }


# --------------------------------------------------------------------- lru
def init_hybrid_cache(cfg: ModelConfig, batch: int,
                      abstract: bool = False) -> Dict[str, Any]:
    n_rec = sum(1 for i in range(cfg.n_layers)
                if cfg.block_pattern[i % len(cfg.block_pattern)] == "rec")
    n_attn = cfg.n_layers - n_rec
    return {
        "h": _maybe((n_rec, batch, cfg.lru_width), jnp.float32, abstract),
        "conv": _maybe((n_rec, batch, cfg.conv_width - 1, cfg.lru_width),
                       cfg.cdtype, abstract),
        "attn": init_window_cache(cfg, n_attn, batch, abstract),
    }


# ------------------------------------------------------------------ encdec
def init_encdec_cache(cfg: ModelConfig, batch: int, max_len: int,
                      abstract: bool = False) -> Dict[str, Any]:
    Ld = cfg.n_layers
    self_shape = (Ld, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    cross_shape = (Ld, batch, cfg.cross_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "self": {"k": _maybe(self_shape, cfg.cdtype, abstract),
                 "v": _maybe(self_shape, cfg.cdtype, abstract)},
        "cross_k": _maybe(cross_shape, cfg.cdtype, abstract),
        "cross_v": _maybe(cross_shape, cfg.cdtype, abstract),
    }


# ---------------------------------------------------------------- dispatch
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False) -> Dict[str, Any]:
    if cfg.family == "ssm":
        return init_rwkv_state(cfg, batch, abstract)
    if cfg.family == "hybrid":
        return init_hybrid_cache(cfg, batch, abstract)
    if cfg.is_encdec:
        return init_encdec_cache(cfg, batch, max_len, abstract)
    init = init_mla_cache if cfg.use_mla else init_full_cache
    nd = cfg.n_dense_layers
    cache = init(cfg, cfg.n_layers - nd, batch, max_len, abstract)
    if nd:
        dense = init(cfg, nd, batch, max_len, abstract)
        cache.update({DENSE + k: v for k, v in dense.items()})
    if cfg.n_experts:
        cache["moe_stats"] = _maybe((1, batch, 2), jnp.int32, abstract)
    return cache
