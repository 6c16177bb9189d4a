"""Decoder-only transformer LMs: dense (GQA/MLA), MoE, and VLM (M-RoPE).

All variants share one block body; layers are stacked and driven by
``jax.lax.scan`` (compact HLO at 126 layers), with optional per-layer remat.
A model with leading dense layers before its expert layers (deepseek-v2)
keeps them as a second group, ``dense_blocks``, scanned first, with their
cache in ``dense_``-prefixed leaves of their own.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed import shard_hint
from .config import ModelConfig
from .kv_cache import DENSE, cache_entries, write_entries
from .layers import (embed_tokens, gqa_attend, gqa_decode_attend,
                     gqa_project, lm_logits, mla_attend, mla_decode_attend,
                     mla_latent, mla_project_q, moe_mlp, rms_norm,
                     swiglu_mlp)


# ------------------------------------------------------------------ blocks
def _mlp(h: jax.Array, p: Dict[str, Any], cfg: ModelConfig, dropless: bool
         ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """A layer's MLP: an expert layer where it has a router, else a dense
    SwiGLU.  Returns (output, aux_loss, expert stats or None)."""
    if "router" in p:
        return moe_mlp(h, p, cfg, dropless)
    return swiglu_mlp(h, p), jnp.zeros((), jnp.float32), None


def _add(total: Optional[jax.Array], stats: Optional[jax.Array]
         ) -> Optional[jax.Array]:
    return total if stats is None else total + stats


def block_fwd(x: jax.Array, p: Dict[str, Any], cfg: ModelConfig,
              positions: jax.Array, mask: jax.Array, dropless: bool = False
              ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], jax.Array,
                         Optional[jax.Array]]:
    """One decoder block (train/prefill). Returns (x, kv_for_cache, aux,
    expert stats or None).  ``dropless``: the serving expert layer, else
    the training one (capacity dispatch and its aux loss).

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
        ff, aux, stats = _mlp(h, p["mlp"], cfg, dropless)
        out = x + ff
    if out.shape[1] > 1:
        out = shard_hint(out, "batch", "carry_seq", None)  # boundary carry
    return out, kv, aux, stats


def block_decode(x: jax.Array, p: Dict[str, Any], cfg: ModelConfig,
                 cache_l: Dict[str, jax.Array], pos: jax.Array
                 ) -> Tuple[jax.Array, Dict[str, jax.Array],
                            Optional[jax.Array]]:
    """One decoder block, single-token decode against the layer's stored
    cache, which it reads and does not write.

    x: (B,1,d); cache_l: the layer's leaves (B, T, C); pos: (B,) absolute
    position of the new token.  Returns (x, the new token's entries by
    leaf name (B, C), expert stats or None).
    """
    positions = pos[:, None]                                    # (B,1)
    with jax.named_scope("attention"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.use_mla:
            kv = mla_latent(h, p["attn"], cfg, positions)
            qq = mla_project_q(h, p["attn"], cfg, positions)
            attn = mla_decode_attend(qq, cache_l["latent"],
                                     cache_l["k_rope"], *kv, pos, p["attn"],
                                     cfg)
        else:
            q, *kv = gqa_project(h, p["attn"], cfg, positions)
            attn = gqa_decode_attend(q, cache_l["k"], cache_l["v"], *kv, pos,
                                     p["attn"], cfg)
        x = x + attn
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        ff, _, stats = _mlp(h, p["mlp"], cfg, dropless=True)
    new = {k: a[:, 0] for k, a in cache_entries(cfg, kv).items()}
    return x + ff, new, stats


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


def _groups(params: Dict[str, Any]) -> Tuple[str, ...]:
    """The stacked layer groups in model order: the leading dense layers
    (``dense_blocks``), if any, then ``blocks``."""
    return tuple(g for g in ("dense_blocks", "blocks") if g in params)


def _cache_of(cache: Dict[str, jax.Array], group: str
              ) -> Dict[str, jax.Array]:
    """One layer group's cache leaves, by their names within the group."""
    if group == "dense_blocks":
        return {k[len(DENSE):]: v for k, v in cache.items()
                if k.startswith(DENSE)}
    return {k: v for k, v in cache.items()
            if not k.startswith(DENSE) and k != "moe_stats"}


def _named(group: str, cache_g: Dict[str, jax.Array]
           ) -> Dict[str, jax.Array]:
    """One group's cache leaves under their names in the whole cache."""
    prefix = DENSE if group == "dense_blocks" else ""
    return {prefix + k: v for k, v in cache_g.items()}


EXPERTS = ("w_gate", "w_up", "w_down")


def _served(p_group: Dict[str, Any]
            ) -> Tuple[Dict[str, Any], Optional[Dict[str, jax.Array]]]:
    """A group's weights as the served scans take them: the per-layer
    stacks for the scan's xs, and apart from them the routed experts'
    stacks (None in a group without experts), which the expert layer
    indexes whole, so that its grouped products read them in place."""
    if "router" not in p_group["mlp"]:
        return p_group, None
    mlp = dict(p_group["mlp"])
    whole = {k: mlp.pop(k) for k in EXPERTS}
    return dict(p_group, mlp=mlp), whole


def _layer(p_l: Dict[str, Any], whole: Optional[Dict[str, jax.Array]],
           i: Any = None) -> Dict[str, Any]:
    """Layer ``i``'s weights: its slice, and the experts' whole stacks."""
    if whole is None:
        return p_l
    return dict(p_l, mlp=dict(p_l["mlp"], **whole, layer=i))


def _stats0(cfg: ModelConfig, batch: int) -> Optional[jax.Array]:
    """Zero expert stats (B, 2) for a model with experts, else None."""
    return jnp.zeros((batch, 2), jnp.int32) if cfg.n_experts else None


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
        h2, _, aux_l, _ = block_fwd(h, p_l, cfg, positions, mask)
        return (h2, aux + aux_l), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    carry = (x, jnp.zeros((), jnp.float32))
    for group in _groups(params):
        carry, _ = jax.lax.scan(body_fn, carry, params[group])
    x, aux = carry
    x = shard_hint(x, "batch", None, None)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, aux


def prefill(params: Dict[str, Any], cfg: ModelConfig,
            inputs: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill: forward + emit the per-layer KV cache (every group's layers
    in model order), and for an expert model ``moe_stats``.
    Returns (last-token logits (B,V), cache)."""
    x, positions = embed_inputs(params, cfg, inputs)
    mask = None   # masks are built lazily (chunked) inside the attention

    def body(carry, xs, whole=None):
        h, st = carry
        p_l, *i = xs
        h2, kv, _, stats = block_fwd(h, _layer(p_l, whole, *i), cfg,
                                     positions, mask, dropless=True)
        return (h2, _add(st, stats)), kv

    carry, cache = (x, _stats0(cfg, x.shape[0])), {}
    for group in _groups(params):
        p_xs, whole = _served(params[group])
        L = jax.tree.leaves(p_xs)[0].shape[0]
        xs = (p_xs,) if whole is None else (p_xs, jnp.arange(L))
        fn = partial(body, whole=whole)
        carry, kvs = jax.lax.scan(jax.checkpoint(fn) if cfg.remat else fn,
                                  carry, xs)
        cache.update(_named(group, cache_entries(cfg, kvs)))
    x, stats = carry
    with jax.named_scope("head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = lm_logits(x[:, -1:], _out_table(params, cfg),
                           cfg.logit_softcap)
    if stats is not None:
        cache["moe_stats"] = stats[None]
    return logits[:, 0], cache


def decode_step(params: Dict[str, Any], cfg: ModelConfig,
                cache: Dict[str, jax.Array], tokens: jax.Array,
                pos: jax.Array
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step. tokens: (B,1); pos: (B,). Returns (logits(B,V), cache).

    Each layer group scans its own weights, and each layer reads its slice
    of the group's stored leaves in place: nothing is written while the
    layers run.  The scan emits only the new token's entries, (L, B, C) per
    leaf, which ``write_entries`` writes at each slot's position
    afterwards, so a donated cache is updated in place and no layer's slab
    is copied.
    """
    x = embed_tokens(tokens, params["embed"], scale=cfg.embed_scale)
    x = x.astype(cfg.cdtype)
    carry, new_cache = (x, _stats0(cfg, x.shape[0])), {}

    def body(carry, xs, kv, whole=None):
        h, st = carry
        p_l, i = xs
        kv_l = {k: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                for k, a in kv.items()}
        h2, new_l, s = block_decode(h, _layer(p_l, whole, i), cfg, kv_l, pos)
        return (h2, _add(st, s)), new_l

    for group in _groups(params):
        kv = _cache_of(cache, group)
        p_xs, whole = _served(params[group])
        L = jax.tree.leaves(kv)[0].shape[0]
        carry, new = jax.lax.scan(partial(body, kv=kv, whole=whole), carry,
                                  (p_xs, jnp.arange(L, dtype=jnp.int32)))
        with jax.named_scope("cache_update"):
            new_cache.update(_named(group, {
                k: write_entries(kv[k], new[k], pos) for k in kv}))
    x, stats = carry
    with jax.named_scope("head"):
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = lm_logits(x, _out_table(params, cfg), cfg.logit_softcap)
    if stats is not None:
        new_cache["moe_stats"] = stats[None]
    return logits[:, 0], new_cache


def _out_table(params: Dict[str, Any], cfg: ModelConfig) -> jax.Array:
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]
