"""Parameter initialization (stacked-layer pytrees).

``init_params`` materializes real arrays; ``abstract_params`` returns
ShapeDtypeStructs via ``jax.eval_shape`` so the multi-pod dry-run never
allocates 405B-parameter models on the host.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .rwkv6 import DECAY_LORA, TM_LORA


def _mk(rng_and_counter, shape, std=0.02, dtype=None, kind="normal"):
    rng, counter, pdtype = rng_and_counter
    counter[0] += 1
    key = jax.random.fold_in(rng, counter[0])
    dtype = dtype or pdtype
    if kind == "normal":
        return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
                * std).astype(dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "const":
        return jnp.full(shape, std, dtype)
    raise ValueError(kind)


def _gqa_attn(mk, cfg: ModelConfig, L: int) -> Dict[str, Any]:
    d, H, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": mk((L, d, H * D)),
        "wk": mk((L, d, Hkv * D)),
        "wv": mk((L, d, Hkv * D)),
        "wo": mk((L, H * D, d), std=0.02 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = mk((L, H * D), kind="zeros")
        p["bk"] = mk((L, Hkv * D), kind="zeros")
        p["bv"] = mk((L, Hkv * D), kind="zeros")
    if cfg.qk_norm:
        p["q_norm"] = mk((L, D), kind="ones")
        p["k_norm"] = mk((L, D), kind="ones")
    return p


def _mla_attn(mk, cfg: ModelConfig, L: int) -> Dict[str, Any]:
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if cfg.q_lora_rank:
        q = {"wq_a": mk((L, d, cfg.q_lora_rank)),
             "q_norm": mk((L, cfg.q_lora_rank), kind="ones"),
             "wq_b": mk((L, cfg.q_lora_rank, H * (dn + dr)))}
    else:
        q = {"wq": mk((L, d, H * (dn + dr)))}
    return {
        **q,
        "wkv_a": mk((L, d, cfg.kv_lora_rank + dr)),
        "kv_norm": mk((L, cfg.kv_lora_rank), kind="ones"),
        "wkv_b": mk((L, cfg.kv_lora_rank, H * (dn + dv))),
        "wo": mk((L, H * dv, d), std=0.02 / (2 * cfg.n_layers) ** 0.5),
    }


def _mlp(mk, cfg: ModelConfig, L: int, gated: bool = True,
         experts: bool = True) -> Dict[str, Any]:
    """The MLP of ``L`` stacked layers: routed experts (those held here)
    with the router over all of them and the shared experts, where the
    config has experts and ``experts``; else a dense MLP of ``d_ff``."""
    d, f = cfg.d_model, cfg.d_ff
    down_std = 0.02 / (2 * cfg.n_layers) ** 0.5
    if cfg.n_experts and experts:
        E = cfg.held_experts * cfg.moe_expert_split
        fs = f // cfg.moe_expert_split
        p = {
            "router": mk((L, d, cfg.n_experts), dtype=jnp.float32),
            "w_gate": mk((L, E, d, fs)),
            "w_up": mk((L, E, d, fs)),
            "w_down": mk((L, E, fs, d), std=down_std),
        }
        if cfg.n_shared_experts:
            fsh = cfg.n_shared_experts * f
            p["shared"] = {"w_gate": mk((L, d, fsh)), "w_up": mk((L, d, fsh)),
                           "w_down": mk((L, fsh, d), std=down_std)}
        return p
    if gated:
        return {"w_gate": mk((L, d, f)), "w_up": mk((L, d, f)),
                "w_down": mk((L, f, d), std=down_std)}
    return {"w_up": mk((L, d, f)), "w_down": mk((L, f, d), std=down_std)}


# --------------------------------------------------------------- families
def _init_lm(mk, cfg: ModelConfig) -> Dict[str, Any]:
    """Embedding, head, and the decoder layers: ``n_dense_layers`` leading
    dense layers of width ``dense_d_ff`` in ``dense_blocks``, the rest in
    ``blocks``."""
    d, V, Ld = cfg.d_model, cfg.vocab_size, cfg.n_dense_layers

    def layers(L: int, mlp_cfg: ModelConfig, experts: bool
               ) -> Dict[str, Any]:
        blocks = {"ln1": mk((L, d), kind="ones"),
                  "ln2": mk((L, d), kind="ones")}
        blocks["attn"] = _mla_attn(mk, cfg, L) if cfg.use_mla \
            else _gqa_attn(mk, cfg, L)
        blocks["mlp"] = _mlp(mk, mlp_cfg, L, experts=experts)
        return blocks

    params = {"embed": mk((V, d)), "ln_f": mk((d,), kind="ones")}
    if Ld:
        params["dense_blocks"] = layers(
            Ld, cfg.with_(d_ff=cfg.dense_d_ff), experts=False)
    params["blocks"] = layers(cfg.n_layers - Ld, cfg, experts=True)
    if not cfg.tie_embeddings:
        params["lm_head"] = mk((V, d))
    return params


def _init_rwkv(mk, cfg: ModelConfig) -> Dict[str, Any]:
    L, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    H, hs = cfg.rwkv_n_heads, cfg.rwkv_head_size
    att = {
        "mu_x": mk((L, d), kind="const", std=0.5),
        "mu_w": mk((L, d), kind="const", std=0.5),
        "mu_k": mk((L, d), kind="const", std=0.5),
        "mu_v": mk((L, d), kind="const", std=0.5),
        "mu_r": mk((L, d), kind="const", std=0.5),
        "mu_g": mk((L, d), kind="const", std=0.5),
        "tm_w1": mk((L, d, 5 * TM_LORA)),
        "tm_w2": mk((L, 5, TM_LORA, d), kind="zeros"),
        "decay_w1": mk((L, d, DECAY_LORA)),
        "decay_w2": mk((L, DECAY_LORA, d), kind="zeros"),
        "w0": mk((L, d), kind="const", std=-5.0),
        "u": mk((L, H, hs), std=0.3),
        "wr": mk((L, d, d)), "wk": mk((L, d, d)), "wv": mk((L, d, d)),
        "wg": mk((L, d, d)),
        "wo": mk((L, d, d), std=0.02 / (2 * L) ** 0.5),
        "ln_x_w": mk((L, d), kind="ones"),
        "ln_x_b": mk((L, d), kind="zeros"),
    }
    ffn = {
        "mu_k": mk((L, d), kind="const", std=0.5),
        "mu_r": mk((L, d), kind="const", std=0.5),
        "w_k": mk((L, d, f)),
        "w_v": mk((L, f, d), std=0.02 / (2 * L) ** 0.5),
        "w_r": mk((L, d, d)),
    }
    return {
        "embed": mk((cfg.vocab_size, d)),
        "lm_head": mk((cfg.vocab_size, d)),
        "ln0_w": mk((d,), kind="ones"), "ln0_b": mk((d,), kind="zeros"),
        "ln_f_w": mk((d,), kind="ones"), "ln_f_b": mk((d,), kind="zeros"),
        "blocks": {
            "ln1_w": mk((L, d), kind="ones"), "ln1_b": mk((L, d), kind="zeros"),
            "ln2_w": mk((L, d), kind="ones"), "ln2_b": mk((L, d), kind="zeros"),
            "att": att, "ffn": ffn,
        },
    }


def _init_hybrid(mk, cfg: ModelConfig) -> Dict[str, Any]:
    d, f, W = cfg.d_model, cfg.d_ff, cfg.lru_width
    kinds = [cfg.block_pattern[i % len(cfg.block_pattern)]
             for i in range(cfg.n_layers)]
    Lr = sum(1 for k in kinds if k == "rec")
    La = cfg.n_layers - Lr
    nb = cfg.n_heads                        # gate blocks
    bw = W // nb
    down_std = 0.02 / (2 * cfg.n_layers) ** 0.5
    rec_blocks = {
        "ln1": mk((Lr, d), kind="zeros"),   # gemma (1+w) convention
        "ln2": mk((Lr, d), kind="zeros"),
        "rec": {
            "w_y": mk((Lr, d, W)),
            "w_x": mk((Lr, d, W)),
            "conv_w": mk((Lr, cfg.conv_width, W), std=0.1),
            "conv_b": mk((Lr, W), kind="zeros"),
            "gate_a_w": mk((Lr, nb, bw, bw), std=bw ** -0.5),
            "gate_a_b": mk((Lr, W), kind="zeros"),
            "gate_i_w": mk((Lr, nb, bw, bw), std=bw ** -0.5),
            "gate_i_b": mk((Lr, W), kind="zeros"),
            "lam": mk((Lr, W), kind="const", std=0.65),
            "w_o": mk((Lr, W, d), std=down_std),
        },
        "mlp": {"w_gate": mk((Lr, d, f)), "w_up": mk((Lr, d, f)),
                "w_down": mk((Lr, f, d), std=down_std)},
    }
    attn_blocks = {
        "ln1": mk((La, d), kind="zeros"),
        "ln2": mk((La, d), kind="zeros"),
        "attn": _gqa_attn(mk, cfg, La),
        "mlp": {"w_gate": mk((La, d, f)), "w_up": mk((La, d, f)),
                "w_down": mk((La, f, d), std=down_std)},
    }
    return {
        "embed": mk((cfg.vocab_size, d)),
        "lm_head": mk((cfg.vocab_size, d)),
        "ln_f": mk((d,), kind="zeros"),
        "rec_blocks": rec_blocks,
        "attn_blocks": attn_blocks,
    }


def _init_encdec(mk, cfg: ModelConfig) -> Dict[str, Any]:
    d, Le, Ld = cfg.d_model, cfg.n_enc_layers, cfg.n_layers
    H, D = cfg.n_heads, cfg.head_dim
    down_std = 0.02 / (2 * (Le + Ld)) ** 0.5
    enc_blocks = {
        "ln1": mk((Le, d), kind="ones"), "ln2": mk((Le, d), kind="ones"),
        "attn": _gqa_attn(mk, cfg, Le),
        "mlp": {"w_up": mk((Le, d, cfg.d_ff)),
                "w_down": mk((Le, cfg.d_ff, d), std=down_std)},
    }
    dec_blocks = {
        "ln1": mk((Ld, d), kind="ones"),
        "ln_cross": mk((Ld, d), kind="ones"),
        "ln2": mk((Ld, d), kind="ones"),
        "attn": _gqa_attn(mk, cfg, Ld),
        "cross": {
            "wq": mk((Ld, d, H * D)), "wk": mk((Ld, d, H * D)),
            "wv": mk((Ld, d, H * D)),
            "wo": mk((Ld, H * D, d), std=down_std),
        },
        "mlp": {"w_up": mk((Ld, d, cfg.d_ff)),
                "w_down": mk((Ld, cfg.d_ff, d), std=down_std)},
    }
    return {
        "embed": mk((cfg.vocab_size, d)),
        "lm_head": mk((cfg.vocab_size, d)),
        "enc_blocks": enc_blocks, "enc_ln_f": mk((d,), kind="ones"),
        "dec_blocks": dec_blocks, "ln_f": mk((d,), kind="ones"),
    }


# ---------------------------------------------------------------- public
def init_params(cfg: ModelConfig, rng: jax.Array) -> Dict[str, Any]:
    counter = [0]
    mk = lambda shape, std=0.02, dtype=None, kind="normal": _mk(
        (rng, counter, cfg.pdtype), shape, std, dtype, kind)
    if cfg.family == "ssm":
        return _init_rwkv(mk, cfg)
    if cfg.family == "hybrid":
        return _init_hybrid(mk, cfg)
    if cfg.is_encdec:
        return _init_encdec(mk, cfg)
    return _init_lm(mk, cfg)


def abstract_params(cfg: ModelConfig) -> Dict[str, Any]:
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0)))


def count_params(cfg: ModelConfig) -> int:
    tree = abstract_params(cfg)
    import math
    return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
