"""Decoder-only transformer LMs: dense (GQA/MLA), MoE, and VLM (M-RoPE).

All variants share one block body; layers are stacked and driven by
``jax.lax.scan`` (compact HLO at 126 layers), with optional per-layer remat.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..distributed import shard_hint
from .config import ModelConfig
from .kv_cache import update_full_cache, update_mla_cache
from .layers import (attention_scores_mask, embed_tokens, gqa_attend,
                     gqa_project, lm_logits, mla_attend, mla_latent,
                     mla_project_q, moe_ffn, rms_norm, swiglu_mlp)


# ------------------------------------------------------------------ blocks
def block_fwd(x: jax.Array, p: Dict[str, Any], cfg: ModelConfig,
              positions: jax.Array, mask: jax.Array
              ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], jax.Array]:
    """One decoder block (train/prefill). Returns (x, kv_for_cache, aux).

    The residual stream is re-constrained at the block boundary with the
    "carry_seq" logical axis: when a per-arch rule maps it to "model", the
    remat-saved scan carry is sequence-sharded (16x less HBM for saved
    activations at 126 layers) while the block *interior* stays batch+head
    sharded — an all-gather on entry / slice on exit, Megatron-SP style.
    """
    if x.shape[1] > 1:
        # pin first (anchors the remat-saved carry's sharding), then gather
        x = shard_hint(x, "batch", "carry_seq", None)
        x = shard_hint(x, "batch", None, None)    # gather for the interior
    with jax.named_scope("attention"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.use_mla:
            qq = mla_project_q(h, p["attn"], cfg, positions)
            latent, k_rope = mla_latent(h, p["attn"], cfg, positions)
            attn = mla_attend(qq, latent, k_rope, mask, p["attn"], cfg)
            kv = (latent, k_rope)
        else:
            q, k, v = gqa_project(h, p["attn"], cfg, positions)
            attn = gqa_attend(q, k, v, mask, p["attn"], cfg)
            kv = (k, v)
        x = x + attn
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            ff, aux = moe_ffn(h, p["mlp"], cfg)
        else:
            ff, aux = swiglu_mlp(h, p["mlp"]), jnp.zeros((), jnp.float32)
        out = x + ff
    if out.shape[1] > 1:
        out = shard_hint(out, "batch", "carry_seq", None)  # boundary carry
    return out, kv, aux


def block_decode(x: jax.Array, p: Dict[str, Any], cfg: ModelConfig,
                 cache_l: Dict[str, jax.Array], pos: jax.Array
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decoder block, single-token decode against the layer's KV cache.

    x: (B,1,d); pos: (B,) absolute position of the new token.
    """
    B = x.shape[0]
    positions = pos[:, None]                                    # (B,1)
    with jax.named_scope("attention"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.use_mla:
            lat_new, rope_new = mla_latent(h, p["attn"], cfg, positions)
            with jax.named_scope("cache_update"):
                lat, ropek = update_mla_cache(cache_l["latent"],
                                              cache_l["k_rope"], lat_new,
                                              rope_new, pos)
            mask = _cache_mask(pos, lat.shape[1])
            qq = mla_project_q(h, p["attn"], cfg, positions)
            attn = mla_attend(qq, lat, ropek, mask, p["attn"], cfg)
            new_cache = {"latent": lat, "k_rope": ropek}
        else:
            q, k_new, v_new = gqa_project(h, p["attn"], cfg, positions)
            with jax.named_scope("cache_update"):
                ck, cv = update_full_cache(cache_l["k"], cache_l["v"],
                                           k_new, v_new, pos)
            mask = _cache_mask(pos, ck.shape[1])
            ck_a = shard_hint(ck, "batch", "kv_seq", None, None)
            cv_a = shard_hint(cv, "batch", "kv_seq", None, None)
            attn = gqa_attend(q, ck_a, cv_a, mask, p["attn"], cfg)
            new_cache = {"k": ck, "v": cv}
        x = x + attn
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.n_experts:
            ff, _ = moe_ffn(h, p["mlp"], cfg)
        else:
            ff = swiglu_mlp(h, p["mlp"])
        return x + ff, new_cache


def _cache_mask(pos: jax.Array, max_len: int) -> jax.Array:
    """(B,1,T) additive mask: valid cache slots are those <= current pos."""
    B = pos.shape[0]
    kpos = jnp.broadcast_to(jnp.arange(max_len, dtype=jnp.int32)[None],
                            (B, max_len))
    kpos = jnp.where(kpos <= pos[:, None], kpos, -1)
    return attention_scores_mask(pos[:, None], kpos, causal=False)


# ------------------------------------------------------------------ model
def embed_inputs(params: Dict[str, Any], cfg: ModelConfig,
                 inputs: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Token (or merged token/patch) embeddings + positions."""
    tokens = inputs["tokens"]
    x = embed_tokens(tokens, params["embed"], scale=cfg.embed_scale)
    if cfg.family == "vlm" and "embeds" in inputs:
        # vision frontend stub: precomputed patch embeddings replace token
        # embeddings where embed_mask is set (dynamic-resolution images)
        x = jnp.where(inputs["embed_mask"][..., None],
                      inputs["embeds"].astype(x.dtype), x)
    if "positions" in inputs:
        positions = inputs["positions"]           # (B,S) or (3,B,S) M-RoPE
    else:
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x.astype(cfg.cdtype), positions


def forward(params: Dict[str, Any], cfg: ModelConfig,
            inputs: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward (train). Returns (hidden, aux_loss)."""
    x, positions = embed_inputs(params, cfg, inputs)
    mask = None   # masks are built lazily (chunked) inside the attention
    # the initial carry must match the block-boundary sharding, or the while
    # loop unifies every iteration's carry to the replicated layout
    x = shard_hint(x, "batch", "carry_seq", None)

    def body(carry, p_l):
        h, aux = carry
        h2, _, aux_l = block_fwd(h, p_l, cfg, positions, mask)
        return (h2, aux + aux_l), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    x = shard_hint(x, "batch", None, None)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, aux


def prefill(params: Dict[str, Any], cfg: ModelConfig,
            inputs: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill: forward + emit the per-layer KV cache.
    Returns (last-token logits (B,V), cache)."""
    x, positions = embed_inputs(params, cfg, inputs)
    mask = None   # masks are built lazily (chunked) inside the attention

    def body(h, p_l):
        h2, kv, _ = block_fwd(h, p_l, cfg, positions, mask)
        return h2, kv

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, kvs = jax.lax.scan(body_fn, x, params["blocks"])
    with jax.named_scope("head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = lm_logits(x[:, -1:], _out_table(params, cfg),
                           cfg.logit_softcap)
    if cfg.use_mla:
        cache = {"latent": kvs[0], "k_rope": kvs[1]}
    else:
        cache = {"k": kvs[0], "v": kvs[1]}
    return logits[:, 0], cache


def decode_step(params: Dict[str, Any], cfg: ModelConfig,
                cache: Dict[str, jax.Array], tokens: jax.Array,
                pos: jax.Array
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step. tokens: (B,1); pos: (B,). Returns (logits(B,V), cache)."""
    x = embed_tokens(tokens, params["embed"], scale=cfg.embed_scale)
    x = x.astype(cfg.cdtype)

    if cfg.decode_carry_cache:
        # §Perf variant: thread the whole cache through the scan *carry* so
        # XLA updates it in place (one buffer), instead of streaming it as
        # xs -> stacked ys (two buffers: 2x cache HBM at 405B/32k).
        def body_carry(carry, xs):
            h, c = carry
            p_l, i = xs
            cache_l = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False), c)
            h2, new_cache_l = block_decode(h, p_l, cfg, cache_l, pos)
            c = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), i, 0), c, new_cache_l)
            return (h2, c), None

        L = jax.tree.leaves(cache)[0].shape[0]
        (x, new_cache), _ = jax.lax.scan(
            body_carry, (x, cache),
            (params["blocks"], jnp.arange(L, dtype=jnp.int32)))
    else:
        def body(h, xs):
            p_l, cache_l = xs
            h2, new_cache_l = block_decode(h, p_l, cfg, cache_l, pos)
            return h2, new_cache_l

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    with jax.named_scope("head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = lm_logits(x, _out_table(params, cfg), cfg.logit_softcap)
    return logits[:, 0], new_cache


def _out_table(params: Dict[str, Any], cfg: ModelConfig) -> jax.Array:
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]
