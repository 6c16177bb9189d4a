"""Qwen2 decoder block: grouped-query attention with q/k/v biases, SwiGLU.

Follows the published description (arXiv:2407.10671; HF ``Qwen2Model``):
pre-norm RMSNorm, rotary embedding in the rotate-half layout, each group of
``num_attention_heads / num_key_value_heads`` query heads sharing one key
and value head.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..flops import Counts, stack_counts
from .common import causal_attention, linear, rms_norm, rope, swiglu


def layer_shapes(c: dict) -> Dict[Tuple[str, ...], Tuple[int, ...]]:
    d, f = c["hidden_size"], c["intermediate_size"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    D = d // H
    return {
        ("blocks", "ln1"): (d,), ("blocks", "ln2"): (d,),
        ("blocks", "attn", "wq"): (d, H * D), ("blocks", "attn", "bq"): (H * D,),
        ("blocks", "attn", "wk"): (d, Hkv * D),
        ("blocks", "attn", "bk"): (Hkv * D,),
        ("blocks", "attn", "wv"): (d, Hkv * D),
        ("blocks", "attn", "bv"): (Hkv * D,),
        ("blocks", "attn", "wo"): (H * D, d),
        ("blocks", "mlp", "w_gate"): (d, f), ("blocks", "mlp", "w_up"): (d, f),
        ("blocks", "mlp", "w_down"): (f, d),
    }


def counts(c: dict) -> Counts:
    d, H = c["hidden_size"], c["num_attention_heads"]
    Hkv, D = c["num_key_value_heads"], d // H
    return stack_counts(
        c, proj=d * H * D + 2 * d * Hkv * D + H * D * d,
        small=2 * d + H * D + 2 * Hkv * D,          # norm gains, q/k/v biases
        attn=4 * H * D, kv=2 * Hkv * D)


def block(x: jax.Array, w: dict, c: dict, quant: Optional[str]) -> jax.Array:
    """One layer over one sequence. x: (T, d) float32."""
    T, d = x.shape
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    D = d // H
    pos = jnp.arange(T)
    a = w["attn"]
    h = rms_norm(x, w["ln1"], c["rms_norm_eps"])
    q = linear(h, a["wq"], a["bq"], quant).reshape(T, H, D)
    k = linear(h, a["wk"], a["bk"], quant).reshape(T, Hkv, D)
    v = linear(h, a["wv"], a["bv"], quant).reshape(T, Hkv, D)
    q = rope(q, pos, c["rope_theta"])
    k = rope(k, pos, c["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=1)       # query head i reads kv head i // g
    v = jnp.repeat(v, H // Hkv, axis=1)
    o = causal_attention(q, k, v).reshape(T, H * D)
    x = x + linear(o, a["wo"], quant=quant)
    return x + swiglu(rms_norm(x, w["ln2"], c["rms_norm_eps"]), w["mlp"], quant)
