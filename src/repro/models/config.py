"""Unified model configuration covering all assigned architecture families.

One dataclass, six families:
  dense   — GQA/MLA decoder LMs (qwen2, qwen3, llama3, minicpm3)
  moe     — mixture-of-experts decoders (olmoe, grok-1, deepseek-v2)
  ssm     — attention-free recurrent LMs (rwkv6)
  hybrid  — RG-LRU + local-attention (recurrentgemma)
  vlm     — M-RoPE decoder backbone, vision frontend stubbed (qwen2-vl)
  audio   — encoder-decoder backbone, audio frontend stubbed (seamless-m4t)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0

    # ---- attention options -------------------------------------------------
    qkv_bias: bool = False          # qwen2
    qk_norm: bool = False           # qwen3
    rope_theta: float = 10_000.0
    attn_logit_softcap: float = 0.0         # grok-style soft capping
    attn_window: int = 0            # >0: sliding-window (local) attention

    # ---- YaRN rope scaling (deepseek-v2); yarn_factor 0: plain rope --------
    yarn_factor: float = 0.0
    yarn_original_len: int = 0      # original_max_position_embeddings
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # ---- MLA (multi-head latent attention, minicpm3, deepseek-v2) ---------
    use_mla: bool = False
    q_lora_rank: int = 0            # 0: queries straight from x (deepseek-v2-lite)
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # ---- MoE ----------------------------------------------------------------
    n_experts: int = 0
    experts_per_token: int = 0
    router_aux_coef: float = 0.01
    moe_norm_topk: bool = True         # renormalise the top-k gate weights
    n_shared_experts: int = 0          # one SwiGLU of n_shared x d_ff beside
                                       # the routed experts
    n_held_experts: int = 0            # routed experts held here (0: all);
    first_held_expert: int = 0         # ids first .. first + held - 1
    n_dense_layers: int = 0            # leading dense SwiGLU layers (params
    dense_d_ff: int = 0                # "dense_blocks"), of this width
    moe_capacity_factor: float = 1.25
    moe_expert_split: int = 1          # "half-expert" sharding: split each
                                       # expert's d_ff k ways so that
                                       # n_experts*k divides the TP axis and
                                       # the expert combine becomes a k-chip
                                       # (not TP-wide) reduction

    # ---- RWKV6 (ssm) ---------------------------------------------------------
    rwkv_head_size: int = 64

    # ---- hybrid (recurrentgemma / griffin) ----------------------------------
    lru_width: int = 0
    conv_width: int = 4
    block_pattern: Tuple[str, ...] = ()      # e.g. ("rec", "rec", "attn")
    embed_scale: bool = False                # gemma-style sqrt(d) embed scaling

    # ---- enc-dec (audio) ------------------------------------------------------
    n_enc_layers: int = 0                    # >0 => encoder-decoder
    cross_len: int = 4_096                   # encoder output length cached for decode

    # ---- modality frontend stub ------------------------------------------------
    frontend: str = "none"                   # none | vision | audio
    rope_sections: Tuple[int, ...] = ()      # M-RoPE (t, h, w) section split

    # ---- perf variants (hillclimb levers; see EXPERIMENTS.md §Perf) --------
    attn_chunk_threshold: int = 4096   # q length above which attention chunks
    attn_online: bool = False          # online-softmax (flash) attention at
                                       # the XLA level: no S x T score tensor
                                       # ever reaches HBM

    # ---- numerics / training -----------------------------------------------
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    logit_softcap: float = 0.0

    # ------------------------------------------------------------------ props
    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch serve a 500k-token context with bounded state?"""
        return self.family in ("ssm", "hybrid")

    @property
    def q_dim(self) -> int:
        if self.use_mla:
            return self.n_heads * (self.qk_nope_dim + self.qk_rope_dim)
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.n_held_experts or self.n_experts

    @property
    def yarn_attn_scale(self) -> float:
        """YaRN's softmax factor mscale(factor, mscale_all_dim)^2; 1 without
        YaRN or without ``mscale_all_dim``."""
        return yarn_get_mscale(self.yarn_factor, self.yarn_mscale_all_dim) ** 2

    @property
    def rwkv_n_heads(self) -> int:
        return self.d_model // self.rwkv_head_size

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    # -------------------------------------------------------------- validate
    def validate(self) -> None:
        assert self.family in ("dense", "moe", "ssm", "hybrid", "vlm", "audio")
        if self.family in ("dense", "moe", "vlm", "audio", "hybrid"):
            assert self.n_heads > 0 and self.head_dim > 0
            if not self.use_mla:
                assert self.n_kv_heads > 0
                assert self.n_heads % self.n_kv_heads == 0
        if self.family == "moe":
            assert self.n_experts > 0 and self.experts_per_token > 0
            assert 0 <= self.first_held_expert
            assert self.first_held_expert + self.held_experts <= self.n_experts
        if self.n_dense_layers:
            assert 0 < self.n_dense_layers < self.n_layers
            assert self.dense_d_ff > 0
        if self.family == "ssm":
            assert self.d_model % self.rwkv_head_size == 0
        if self.family == "hybrid":
            assert self.block_pattern and self.lru_width > 0
        if self.use_mla:
            assert self.q_lora_rank >= 0 and self.kv_lora_rank > 0
            assert self.qk_nope_dim > 0 and self.qk_rope_dim > 0
            assert self.v_head_dim > 0
        if self.rope_sections:
            assert sum(self.rope_sections) * 2 == self._rope_dim(), \
                f"M-RoPE sections {self.rope_sections} must sum to head_dim/2"

    def _rope_dim(self) -> int:
        return self.qk_rope_dim if self.use_mla else self.head_dim


def yarn_get_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention magnitude factor, as DeepSeek-V2 publishes it."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell (seq_len × global_batch, and which step it lowers)."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode

    @property
    def is_train(self) -> bool:
        return self.kind == "train"


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


def shape_by_name(name: str) -> ShapeConfig:
    for s in ALL_SHAPES:
        if s.name == name:
            return s
    raise KeyError(f"unknown shape {name!r}")
