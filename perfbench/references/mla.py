"""MiniCPM3 decoder block: multi-head latent attention with the latent
expanded to per-head keys and values, SwiGLU.

Follows the published description (hf:openbmb/MiniCPM3-4B, the DeepSeek-V2
form of MLA): queries through a low-rank path with its own RMSNorm; keys and
values from one compressed latent (RMSNorm'd) that ``wkv_b`` expands to
``qk_nope_head_dim`` key and ``v_head_dim`` value channels per head; a
rotary key of ``qk_rope_head_dim`` channels shared by all heads.  The
latent is expanded here, so this is independent of the served, weight-
absorbed form.  MiniCPM's muP scalings are not applied (see the
configuration file's ``assumed``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..flops import Counts, stack_counts
from .common import causal_attention, linear, rms_norm, rope, swiglu


def layer_shapes(c: dict) -> Dict[Tuple[str, ...], Tuple[int, ...]]:
    d, f, H = c["hidden_size"], c["intermediate_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    ql, r = c["q_lora_rank"], c["kv_lora_rank"]
    return {
        ("blocks", "ln1"): (d,), ("blocks", "ln2"): (d,),
        ("blocks", "attn", "wq_a"): (d, ql),
        ("blocks", "attn", "q_norm"): (ql,),
        ("blocks", "attn", "wq_b"): (ql, H * (dn + dr)),
        ("blocks", "attn", "wkv_a"): (d, r + dr),
        ("blocks", "attn", "kv_norm"): (r,),
        ("blocks", "attn", "wkv_b"): (r, H * (dn + dv)),
        ("blocks", "attn", "wo"): (H * dv, d),
        ("blocks", "mlp", "w_gate"): (d, f), ("blocks", "mlp", "w_up"): (d, f),
        ("blocks", "mlp", "w_down"): (f, d),
    }


def counts(c: dict) -> Counts:
    d, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    ql, r = c["q_lora_rank"], c["kv_lora_rank"]
    return stack_counts(
        c, proj=d * ql + ql * H * (dn + dr) + d * (r + dr)
        + r * H * (dn + dv) + H * dv * d,
        small=2 * d + ql + r,                        # norm gains
        attn=2 * H * (dn + dr) + 2 * H * dv, kv=r + dr)


def block(x: jax.Array, w: dict, c: dict, quant: Optional[str]) -> jax.Array:
    """One layer over one sequence. x: (T, d) float32."""
    T = x.shape[0]
    H = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r, eps, theta = c["kv_lora_rank"], c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(T)
    a = w["attn"]
    h = rms_norm(x, w["ln1"], eps)
    q_low = rms_norm(linear(h, a["wq_a"], quant=quant), a["q_norm"], eps)
    q = linear(q_low, a["wq_b"], quant=quant).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, theta)], -1)
    kv_a = linear(h, a["wkv_a"], quant=quant)
    latent = rms_norm(kv_a[:, :r], a["kv_norm"], eps)
    k_rope = rope(kv_a[:, None, r:], pos, theta)                  # (T, 1, dr)
    kv = linear(latent, a["wkv_b"], quant=quant).reshape(T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (T, H, dr))], -1)
    o = causal_attention(q, k, kv[..., dn:]).reshape(T, H * dv)
    x = x + linear(o, a["wo"], quant=quant)
    return x + swiglu(rms_norm(x, w["ln2"], eps), w["mlp"], quant)
