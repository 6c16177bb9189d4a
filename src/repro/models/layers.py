"""Shared neural-net layers (pure JAX, sharding-hint annotated).

Numerics policy: parameters/compute in bf16, softmax + normalization +
recurrence states in fp32.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed import shard_hint
from .config import ModelConfig, yarn_get_mscale

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------- norms
def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             *, offset: float = 0.0) -> jax.Array:
    """RMSNorm in fp32; ``offset=1`` gives the Gemma (1+w) convention."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (offset + weight.astype(jnp.float32))).astype(dtype)


def group_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               n_groups: int, eps: float) -> jax.Array:
    """GroupNorm over the last dim (RWKV's ln_x), fp32."""
    dtype = x.dtype
    *lead, d = x.shape
    x = x.astype(jnp.float32).reshape(*lead, n_groups, d // n_groups)
    mean = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x.reshape(*lead, d)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


# ----------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


@functools.lru_cache(maxsize=None)
def yarn_rope(cfg: ModelConfig) -> Tuple[np.ndarray, float]:
    """YaRN's inverse frequencies (D/2,) over the rotary channels, and the
    factor on cos and sin, as DeepSeek-V2 publishes them: a linear ramp
    between the correction dims of ``beta_fast`` and ``beta_slow``
    rotations at the original length takes each channel from its own
    frequency (fast channels) to that frequency over ``factor`` (slow
    ones).  Computed once per configuration."""
    d, base, f = cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def corr_dim(rotations: float) -> float:
        return d * math.log(cfg.yarn_original_len
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra / f * ramp + extra * (1 - ramp)
    scale = yarn_get_mscale(f, cfg.yarn_mscale) \
        / yarn_get_mscale(f, cfg.yarn_mscale_all_dim)
    inv = inv.astype(np.float32)
    inv.setflags(write=False)           # one array per config, shared
    return inv, scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               sections: Tuple[int, ...] = (),
               yarn: Optional[Tuple[np.ndarray, float]] = None) -> jax.Array:
    """Rotary embedding (NeoX half-rotation layout).

    x: (B, S, H, D); positions: (B, S) — or (3, B, S) for M-RoPE, where the
    three planes are the temporal / height / width position components and
    ``sections`` splits the D/2 frequency channels among them (Qwen2-VL).
    ``yarn``: frequencies and cos/sin factor from :func:`yarn_rope`.
    """
    dtype = x.dtype
    d = x.shape[-1]
    inv = rope_freqs(d, theta) if yarn is None \
        else jnp.asarray(yarn[0])                               # (D/2,)
    if positions.ndim == 3:
        assert sections and sum(sections) == d // 2, (sections, d)
        # freqs per component plane, then select by section
        f = positions[..., None].astype(jnp.float32) * inv      # (3, B, S, D/2)
        sel = jnp.repeat(jnp.arange(len(sections)),
                         jnp.asarray(sections), total_repeat_length=d // 2)
        idx = jnp.broadcast_to(sel[None, None, None, :],
                               (1,) + f.shape[1:3] + (d // 2,))
        freqs = jnp.take_along_axis(f, idx, axis=0)[0]          # (B, S, D/2)
    else:
        freqs = positions[..., None].astype(jnp.float32) * inv  # (B, S, D/2)
    cos = jnp.cos(freqs)[:, :, None, :]                         # (B, S, 1, D/2)
    sin = jnp.sin(freqs)[:, :, None, :]
    if yarn is not None and yarn[1] != 1.0:
        cos, sin = cos * yarn[1], sin * yarn[1]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ------------------------------------------------------------- attention
def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        scores = jnp.tanh(scores / cap) * cap
    return scores


def attention_scores_mask(q_pos: jax.Array, k_pos: jax.Array,
                          window: int = 0,
                          causal: bool = True) -> jax.Array:
    """Additive fp32 mask (..., Q, K) built from absolute positions.
    Negative k positions mark unwritten cache slots (always invalid)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    valid = kp >= 0
    if causal:
        valid &= kp <= qp
    if window and window > 0:
        valid &= kp > qp - window
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


def multi_head_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         mask: Optional[jax.Array], *,
                         softcap: float = 0.0,
                         scale: Optional[float] = None) -> jax.Array:
    """Grouped-query attention. q: (B,S,Hq,D), k/v: (B,T,Hkv,D[v]).

    Softmax in fp32.  mask: broadcastable to (B, 1|H, S, T), additive.
    """
    B, S, Hq, D = q.shape
    _, T, Hkv, Dv = v.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, S, Hkv, g, D)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = _softcap(scores, softcap)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None, None]
        elif mask.ndim == 3:
            mask = mask[:, None, None]                 # (B,1,1,S,T)
        elif mask.ndim == 4:
            mask = mask[:, :, None]                    # (B,H?,1,S,T)
        scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, Hq, Dv)


# ------------------------------------------------------------ projections
def linear(x: jax.Array, w: jax.Array,
           b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def gqa_project(x: jax.Array, p: dict, cfg: ModelConfig,
                positions: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Project + norm + rope. Returns q (B,S,H,D), k/v (B,S,Hkv,D)."""
    B, S, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(x, p["wq"], p.get("bq")).reshape(B, S, H, D)
    k = linear(x, p["wk"], p.get("bk")).reshape(B, S, Hkv, D)
    v = linear(x, p["wv"], p.get("bv")).reshape(B, S, Hkv, D)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_sections)
    q = shard_hint(q, "batch", None, "tp", None)
    return q, k, v


# XLA-level flash attention: above this q length, attention runs as a
# remat'd scan over q blocks with lazily-built per-block masks, so neither
# the (S,T) score tensor nor the (S,T) mask is ever materialized in full.
ATTN_CHUNK_THRESHOLD = 4096
ATTN_CHUNK = 128


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
         q_pos: Optional[jax.Array] = None,
         k_pos: Optional[jax.Array] = None,
         causal: bool = True, window: int = 0,
         softcap: float = 0.0, scale: Optional[float] = None,
         threshold: Optional[int] = None) -> jax.Array:
    """Scaled-dot-product GQA attention with lazy masks + q-block chunking.

    q: (B,S,Hq,D); k/v: (B,T,Hkv,D). ``q_pos``/``k_pos``: (B,S)/(B,T)
    absolute positions (negative k positions = invalid slots).  When both
    are None and not causal/windowed, no mask is built at all.
    """
    B, S = q.shape[:2]
    T = k.shape[1]

    def mask_for(qp: Optional[jax.Array]) -> Optional[jax.Array]:
        if not causal and not window and k_pos is None:
            return None
        kp = k_pos
        if kp is None:
            kp = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None],
                                  (B, T))
        if qp is None:
            qp = jnp.broadcast_to(
                jnp.arange(T - S, T, dtype=jnp.int32)[None], (B, S))
        return attention_scores_mask(qp, kp, window=window, causal=causal)

    thr = ATTN_CHUNK_THRESHOLD if threshold is None else threshold
    if S <= thr or S % ATTN_CHUNK != 0:
        return multi_head_attention(q, k, v, mask_for(q_pos),
                                    softcap=softcap, scale=scale)

    C = ATTN_CHUNK
    nb = S // C
    qb = jnp.moveaxis(q.reshape(B, nb, C, *q.shape[2:]), 1, 0)
    qp = q_pos
    if qp is None:
        qp = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    qpb = jnp.moveaxis(qp.reshape(B, nb, C), 1, 0)

    def body(_, xs):
        qi, qpi = xs
        out = multi_head_attention(qi, k, v, mask_for(qpi),
                                   softcap=softcap, scale=scale)
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qb, qpb))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, q.shape[2], v.shape[-1])


def sdpa_online(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool = True, window: int = 0,
                softcap: float = 0.0, scale: Optional[float] = None,
                bq: int = 128, bk: int = 512) -> jax.Array:
    """Flash-style attention in pure XLA: nested scans over (q, kv) blocks
    with the online-softmax running state (m, l, acc) carried between kv
    blocks.  Partial (bq x bk) score tiles are fusion-local — the S x T
    score tensor never reaches HBM, exactly the Pallas kernel's schedule.
    Wrapped in remat per q block so the backward recomputes tiles too.
    """
    B, S, Hq, D = q.shape
    _, T, Hkv, Dv = v.shape
    g = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale
    bq = min(bq, S)
    bk = min(bk, T)
    if S % bq or T % bk:
        return sdpa(q, k, v, causal=causal, window=window, softcap=softcap,
                    scale=scale)
    nq, nk = S // bq, T // bk
    q_off = T - S

    qb = jnp.moveaxis(q.reshape(B, nq, bq, Hq, D), 1, 0)        # (nq,B,bq,H,D)
    kb = jnp.moveaxis(k.reshape(B, nk, bk, Hkv, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, bk, Hkv, Dv), 1, 0)

    def q_block(_, xs):
        qi_idx, qblk = xs                                        # (B,bq,H,D)
        qg = qblk.reshape(B, bq, Hkv, g, D)

        def kv_block(carry, kxs):
            m, l, acc = carry
            ki_idx, kblk, vblk = kxs
            s = jnp.einsum("bshgd,bthd->bhgst", qg, kblk,
                           preferred_element_type=jnp.float32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            qp = qi_idx * bq + q_off \
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kp = ki_idx * bk \
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            valid = jnp.ones((bq, bk), bool)
            if causal:
                valid &= kp <= qp
            if window:
                valid &= kp > qp - window
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgst,bthd->bhgsd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, bq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (jnp.arange(nk, dtype=jnp.int32), kb, vb))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return None, jnp.moveaxis(out, 3, 1).reshape(B, bq, Hq, Dv)

    _, outs = jax.lax.scan(jax.checkpoint(q_block), None,
                           (jnp.arange(nq, dtype=jnp.int32), qb))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq, Dv).astype(q.dtype)


def gqa_attend(q: jax.Array, k: jax.Array, v: jax.Array,
               mask: Optional[jax.Array], p: dict,
               cfg: ModelConfig, *,
               q_pos: Optional[jax.Array] = None,
               k_pos: Optional[jax.Array] = None,
               causal: bool = True) -> jax.Array:
    """Attention + output projection. Returns (B,S,d).

    If ``mask`` is given it is used directly (decode paths); otherwise the
    mask is built lazily from positions inside :func:`sdpa` (chunked for
    long q), or via the online-softmax (flash) path when ``cfg.attn_online``.
    """
    B, S, H, D = q.shape
    if mask is not None:
        out = multi_head_attention(q, k, v, mask,
                                   softcap=cfg.attn_logit_softcap)
    elif cfg.attn_online and S > 1 and q_pos is None and k_pos is None:
        out = sdpa_online(q, k, v, causal=causal, window=cfg.attn_window,
                          softcap=cfg.attn_logit_softcap)
    else:
        out = sdpa(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                   window=cfg.attn_window, softcap=cfg.attn_logit_softcap,
                   threshold=cfg.attn_chunk_threshold)
    out = linear(out.reshape(B, S, H * v.shape[-1]), p["wo"])
    return shard_hint(out, "batch", "seq", None)


def _decode_probs(s: jax.Array, s_new: jax.Array, pos: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Softmax over a slot's stored positions below its own and the new
    token's own key.  s: (B, ..., T) float32 scores against the stored
    rows, s_new: (B, ..., 1) against the new key, pos: (B,).  Returns the
    probabilities (B, ..., T) and (B, ..., 1)."""
    T = s.shape[-1]
    valid = jnp.arange(T, dtype=jnp.int32)[None] < pos[:, None]      # (B,T)
    valid = valid.reshape(valid.shape[:1] + (1,) * (s.ndim - 2) + (T,))
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.maximum(s.max(-1, keepdims=True), s_new)
    e, e_new = jnp.exp(s - m), jnp.exp(s_new - m)
    total = e.sum(-1, keepdims=True) + e_new
    return e / total, e_new / total


def gqa_decode_attend(q: jax.Array, ck: jax.Array, cv: jax.Array,
                      k_new: jax.Array, v_new: jax.Array, pos: jax.Array,
                      p: dict, cfg: ModelConfig) -> jax.Array:
    """Single-token GQA attention + output projection against one layer's
    stored cache, which it reads and does not write.  Returns (B,1,d).

    q: (B,1,H,D); ck/cv: (B,T,Hkv*D) as the cache stores them, valid below
    each slot's ``pos`` (B,); k_new/v_new: (B,1,Hkv,D), the new token's
    own, which join the softmax beside the stored rows.  Each kv head's
    queries are set block-diagonally over a stored row's Hkv*D entries, so
    both products contract or keep the row's minor dimension whole and
    read the rows in place; that costs Hkv x the head's own FLOPs, which a
    bandwidth-bound decode step does not feel.
    """
    B, _, H, D = q.shape
    Hkv = k_new.shape[2]
    g = H // Hkv
    scale = D ** -0.5
    cap = cfg.attn_logit_softcap
    ck = shard_hint(ck, "batch", "kv_seq", None)
    cv = shard_hint(cv, "batch", "kv_seq", None)
    qg = q[:, 0].reshape(B, Hkv, g, D)
    eye = jnp.eye(Hkv, dtype=q.dtype)
    qz = jnp.einsum("bhgd,hk->bhgkd", qg, eye).reshape(B, Hkv, g, Hkv * D)
    s = jnp.einsum("bhgc,btc->bhgt", qz, ck,
                   preferred_element_type=jnp.float32) * scale
    s_new = jnp.einsum("bhgd,bhd->bhg", qg, k_new[:, 0],
                       preferred_element_type=jnp.float32)[..., None] * scale
    probs, p_new = _decode_probs(_softcap(s, cap), _softcap(s_new, cap), pos)
    o = jnp.einsum("bhgt,btc->bhgc", probs.astype(cv.dtype), cv,
                   preferred_element_type=jnp.float32)
    o = jnp.einsum("bhgkd,hk->bhgd", o.reshape(B, Hkv, g, Hkv, D),
                   eye.astype(jnp.float32))
    o = o + p_new * v_new[:, 0, :, None, :].astype(jnp.float32)
    out = linear(o.astype(q.dtype).reshape(B, 1, H * D), p["wo"])
    return shard_hint(out, "batch", "seq", None)


def gqa_attention_block(x: jax.Array, p: dict, cfg: ModelConfig,
                        positions: jax.Array, mask: Optional[jax.Array],
                        ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full GQA attention (train/prefill). Returns (output, (k, v))."""
    q, k, v = gqa_project(x, p, cfg, positions)
    return gqa_attend(q, k, v, mask, p, cfg), (k, v)


# ----------------------------------------------------------------- MLA
def mla_rope(x: jax.Array, positions: jax.Array,
             cfg: ModelConfig) -> jax.Array:
    """Rotary embedding of MLA's rotary channels, YaRN-scaled if set."""
    return apply_rope(x, positions, cfg.rope_theta,
                      yarn=yarn_rope(cfg) if cfg.yarn_factor else None)


def mla_project_q(x: jax.Array, p: dict, cfg: ModelConfig,
                  positions: jax.Array) -> jax.Array:
    """Queries (B,S,H,nope+rope): through the low-rank path, or straight
    from ``x`` by ``wq`` where ``q_lora_rank`` is 0."""
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        ql = rms_norm(linear(x, p["wq_a"]), p["q_norm"], cfg.norm_eps)
        q = linear(ql, p["wq_b"]).reshape(B, S, H, dn + dr)
    else:
        q = linear(x, p["wq"]).reshape(B, S, H, dn + dr)
    q_rope = mla_rope(q[..., dn:], positions, cfg)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    return shard_hint(q, "batch", None, "tp", None)


def mla_latent(x: jax.Array, p: dict, cfg: ModelConfig,
               positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The compressed KV: latent (B,S,kv_lora) + shared k_rope (B,S,1,dr).
    This pair is exactly what MLA caches for decode."""
    kv_a = linear(x, p["wkv_a"])
    latent = rms_norm(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = mla_rope(kv_a[..., None, cfg.kv_lora_rank:], positions, cfg)
    return latent, k_rope


def mla_attend(qq: jax.Array, latent: jax.Array, k_rope: jax.Array,
               mask: Optional[jax.Array], p: dict, cfg: ModelConfig, *,
               q_pos: Optional[jax.Array] = None,
               k_pos: Optional[jax.Array] = None,
               causal: bool = True) -> jax.Array:
    """Latent-space ("weight-absorbed") MLA attention.

    Instead of expanding the latent to per-head K/V — (B,T,H,dn+dv), 343 GB
    at 32k context for MiniCPM3 — the up-projection W_uk is absorbed into
    the query and W_uv into the output:

        scores = (q_nope @ W_uk) . latent + q_rope . k_rope
        out    = (softmax(scores) @ latent) @ W_uv

    so the only T-sized tensors are the latent (r=256/channel) and the
    shared rotary key — exactly what the MLA cache stores.  Long q is
    chunked like :func:`sdpa`.  The softmax scale carries YaRN's mscale².
    """
    B, S = qq.shape[:2]
    H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    T = latent.shape[1]
    scale = (dn + dr) ** -0.5 * cfg.yarn_attn_scale

    w = p["wkv_b"].reshape(r, H, dn + dv)
    w_uk, w_uv = w[..., :dn], w[..., dn:]
    q_nope, q_rope = qq[..., :dn], qq[..., dn:]
    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk.astype(qq.dtype))
    kr = k_rope[:, :, 0]                                     # (B,T,dr)

    def mask_for(qp):
        if mask is not None:
            return mask
        if not causal and k_pos is None:
            return None
        kp = k_pos
        if kp is None:
            kp = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None],
                                  (B, T))
        if qp is None:
            qp = jnp.broadcast_to(
                jnp.arange(T - S, T, dtype=jnp.int32)[None], (B, S))
        return attention_scores_mask(qp, kp, causal=causal)

    def attend_block(qa, qr, qp):
        s = (jnp.einsum("bshr,btr->bhst", qa, latent,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshp,btp->bhst", qr, kr,
                          preferred_element_type=jnp.float32)) * scale
        m = mask_for(qp)
        if m is not None:
            if m.ndim == 3:
                m = m[:, None]
            s = s + m
        probs = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        ctx = jnp.einsum("bhst,btr->bshr", probs.astype(latent.dtype),
                         latent)
        return jnp.einsum("bshr,rhv->bshv", ctx, w_uv.astype(ctx.dtype))

    if S <= cfg.attn_chunk_threshold or S % ATTN_CHUNK != 0 \
            or mask is not None:
        out = attend_block(q_abs, q_rope, q_pos)
    else:
        C = ATTN_CHUNK
        nb = S // C
        qp = q_pos
        if qp is None:
            qp = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                  (B, S))
        xs = (jnp.moveaxis(q_abs.reshape(B, nb, C, H, r), 1, 0),
              jnp.moveaxis(q_rope.reshape(B, nb, C, H, dr), 1, 0),
              jnp.moveaxis(qp.reshape(B, nb, C), 1, 0))

        def body(_, x):
            return None, attend_block(*x)

        _, outs = jax.lax.scan(jax.checkpoint(body), None, xs)
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, dv)

    out = linear(out.reshape(B, S, H * dv), p["wo"])
    return shard_hint(out, "batch", "seq", None)


def mla_decode_attend(qq: jax.Array, clat: jax.Array, crope: jax.Array,
                      lat_new: jax.Array, rope_new: jax.Array,
                      pos: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Single-token latent-space MLA attention (as :func:`mla_attend`)
    against one layer's stored cache, which it reads and does not write.
    Returns (B,1,d).

    qq: (B,1,H,nope+rope); clat (B,T,r) and crope (B,T,dr) as the cache
    stores them, valid below each slot's ``pos`` (B,); lat_new (B,1,r) and
    rope_new (B,1,1,dr), the new token's own, which join the softmax
    beside the stored rows.
    """
    B = qq.shape[0]
    H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    scale = (dn + dr) ** -0.5 * cfg.yarn_attn_scale
    clat = shard_hint(clat, "batch", "kv_seq", None)
    crope = shard_hint(crope, "batch", "kv_seq", None)

    w = p["wkv_b"].reshape(r, H, dn + dv)
    w_uk, w_uv = w[..., :dn], w[..., dn:]
    q_abs = jnp.einsum("bhn,rhn->bhr", qq[:, 0, :, :dn],
                       w_uk.astype(qq.dtype))
    q_rope = qq[:, 0, :, dn:]
    lat1, rope1 = lat_new[:, 0], rope_new[:, 0, 0]
    f32 = jnp.float32
    s = (jnp.einsum("bhr,btr->bht", q_abs, clat, preferred_element_type=f32)
         + jnp.einsum("bhp,btp->bht", q_rope, crope,
                      preferred_element_type=f32)) * scale
    s_new = (jnp.einsum("bhr,br->bh", q_abs, lat1, preferred_element_type=f32)
             + jnp.einsum("bhp,bp->bh", q_rope, rope1,
                          preferred_element_type=f32))[..., None] * scale
    probs, p_new = _decode_probs(s, s_new, pos)
    ctx = jnp.einsum("bht,btr->bhr", probs.astype(clat.dtype), clat,
                     preferred_element_type=f32)
    ctx = (ctx + p_new * lat1[:, None].astype(f32)).astype(qq.dtype)
    out = jnp.einsum("bhr,rhv->bhv", ctx, w_uv.astype(ctx.dtype))
    out = linear(out.reshape(B, 1, H * dv), p["wo"])
    return shard_hint(out, "batch", "seq", None)


def mla_attention_block(x: jax.Array, p: dict, cfg: ModelConfig,
                        positions: jax.Array, mask: Optional[jax.Array],
                        ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full MLA attention (train/prefill). Returns (output, (latent, k_rope))."""
    qq = mla_project_q(x, p, cfg, positions)
    latent, k_rope = mla_latent(x, p, cfg, positions)
    return mla_attend(qq, latent, k_rope, mask, p, cfg), (latent, k_rope)


# ----------------------------------------------------------------- MLPs
def swiglu_mlp(x: jax.Array, p: dict) -> jax.Array:
    h = jax.nn.silu(linear(x, p["w_gate"])) * linear(x, p["w_up"])
    h = shard_hint(h, "batch", None, "tp")
    return shard_hint(linear(h, p["w_down"]), "batch", "seq", None)


def gelu_mlp(x: jax.Array, p: dict) -> jax.Array:
    h = jax.nn.gelu(linear(x, p["w_up"]))
    h = shard_hint(h, "batch", None, "tp")
    return shard_hint(linear(h, p["w_down"]), "batch", "seq", None)


# ------------------------------------------------------------------ MoE
def moe_route(x: jax.Array, p: dict, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The gate over all ``n_experts`` routed experts, in float32 from the
    float32 router: softmax probabilities (..., E) and each token's top-k
    weights and expert ids (..., K), the weights renormalised to sum to 1
    where ``moe_norm_topk`` says so."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.moe_norm_topk:
        top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)
    return probs, top_w, top_e


def _held_id(top_e: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Each assignment's index among the held experts; ``held_experts``
    (past the last) for an expert another chip holds."""
    local = top_e - cfg.first_held_expert
    held = (local >= 0) & (local < cfg.held_experts)
    return jnp.where(held, local, cfg.held_experts)


def _expert_weights(p: dict, cfg: ModelConfig
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(..., E, d, f), (..., E, d, f), (..., E, f, d): half-expert weights
    (stored as ``moe_expert_split`` sub-experts per expert) joined back
    along f."""
    ks = cfg.moe_expert_split
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if ks == 1:
        return wg, wu, wd
    *lead, Eks, d, fs = wg.shape
    shape = tuple(lead) + (Eks // ks,)
    join = lambda w: w.reshape(shape + (ks, d, fs)).swapaxes(-3, -2) \
        .reshape(shape + (d, ks * fs))                           # noqa: E731
    return join(wg), join(wu), wd.reshape(shape + (ks * fs, d))


def moe_ffn(x: jax.Array, p: dict, cfg: ModelConfig,
            capacity_factor: Optional[float] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed SwiGLU experts, sort-based capacity dispatch (training).

    Tokens are grouped per batch row; within each group, (token, expert)
    assignments are sorted by expert id and scattered into an
    (E, C) capacity buffer — pure XLA ops (argsort/cumsum/scatter), no ragged
    support needed.  Over-capacity assignments are dropped (counted in the
    aux output), and so are assignments to experts not held here.  Returns
    (output, aux_loss).  The served path uses :func:`moe_dropless`.
    """
    B, S, d = x.shape
    E, El, K = cfg.n_experts, cfg.held_experts, cfg.experts_per_token
    cf = capacity_factor if capacity_factor is not None \
        else cfg.moe_capacity_factor
    C = max(int(S * K / E * cf), K)

    probs, top_w, top_e = moe_route(x, p, cfg)                     # (B,S,K)

    # ---- load-balancing aux loss (Switch-style), fp32
    me = probs.mean(axis=(0, 1))                                   # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
        jnp.ones((B * S * K,), jnp.float32)) / (B * S * K)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # ---- per-group sort-based dispatch
    flat_e = _held_id(top_e, cfg).reshape(B, S * K)                # (B, N)
    flat_w = top_w.reshape(B, S * K).astype(x.dtype)
    tok_id = jnp.repeat(jnp.arange(S), K)[None].repeat(B, 0)       # (B, N)

    order = jnp.argsort(flat_e, axis=-1)                           # stable
    e_sorted = jnp.take_along_axis(flat_e, order, -1)
    t_sorted = jnp.take_along_axis(tok_id, order, -1)
    w_sorted = jnp.take_along_axis(flat_w, order, -1)

    # position within expert = index - start-of-segment (the assignments are
    # sorted by expert id, so segments are contiguous).  O(N) — no (N,E)
    # one-hot cumsum, which would be TB-scale at 32k x top-8 x 64e.
    N = e_sorted.shape[1]
    idx = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[None], e_sorted.shape)
    is_new = jnp.concatenate(
        [jnp.ones((B, 1), bool), e_sorted[:, 1:] != e_sorted[:, :-1]], axis=1)
    seg_start = jax.lax.cummax(jnp.where(is_new, idx, 0), axis=1)
    pos_in_e = idx - seg_start
    keep = (pos_in_e < C) & (e_sorted < El)
    slot = jnp.where(keep, e_sorted * C + pos_in_e, 0)             # (B,N)

    # gathers/scatters are expressed per batch row via vmap so they lower
    # with operand batching dims — GSPMD keeps the batch axis sharded
    # (a flat .at[arange(B)[:,None], idx] scatter replicates the (B,S,d)
    # operand and all-reduces it over BOTH mesh axes: measured 3 TB/step
    # of fp32 all-reduce on grok-1 before this change)
    xs = jax.vmap(lambda row, t: jnp.take(row, t, axis=0))(
        x, t_sorted)                                               # (B,N,d)
    buf = jax.vmap(lambda s, v: jnp.zeros((El * C, d), x.dtype)
                   .at[s].add(v))(slot, jnp.where(keep[..., None], xs, 0))
    buf = buf.reshape(B, El, C, d)
    buf = shard_hint(buf, "batch", "experts", None, None)

    # ---- expert SwiGLU: (B,E,C,d) x (E,d,f)
    ks = cfg.moe_expert_split
    if ks > 1:
        # half-expert sharding: weights are stored pre-split as
        # (E*ks, d, f/ks); replicate each expert's tokens to its ks
        # sub-experts so compute is sub-expert-local, then reduce the ks
        # partial down-projections — a ks-chip reduction instead of a
        # TP-wide all-reduce when E*ks divides the "model" axis.
        bufs = jnp.repeat(buf, ks, axis=1)            # (B,E*ks,C,d)
        bufs = shard_hint(bufs, "batch", "experts", None, None)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", bufs,
                                   p["w_gate"].astype(x.dtype))) \
            * jnp.einsum("becd,edf->becf", bufs, p["w_up"].astype(x.dtype))
        y_s = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(x.dtype))
        y_e = y_s.reshape(B, El, ks, C, d).sum(axis=2)
    else:
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf,
                                   p["w_gate"].astype(x.dtype))) \
            * jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(x.dtype))
        y_e = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(x.dtype))
    y_e = shard_hint(y_e, "batch", "experts", None, None).reshape(B, El * C, d)

    # ---- gather back + weighted combine (vmap'd: batch stays sharded)
    ys = jax.vmap(lambda row, s: jnp.take(row, s, axis=0))(y_e, slot)
    ys = ys * (w_sorted * keep.astype(x.dtype))[..., None]
    out = jax.vmap(lambda t, v: jnp.zeros((S, d), x.dtype)
                   .at[t].add(v))(t_sorted, ys)
    return shard_hint(out, "batch", "seq", None), aux


def moe_dropless(x: jax.Array, p: dict, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """Routed SwiGLU experts with nothing dropped (prefill and decode).

    Routes every token over all ``n_experts`` (:func:`moe_route`), sorts the
    call's assignments to the experts held here by expert, across the whole
    batch, and runs the held experts as grouped matrix products over
    exactly those rows (``jax.lax.ragged_dot``).  Assignments to experts
    another chip holds sort last, outside every group, and add nothing.
    With ``p["layer"]`` the expert weights are the whole stacks of every
    layer, (L, E, ...), and that layer's experts are used.
    Returns the weighted sum (B,S,d) and, per batch row, int32 (B, 2): the
    row's assignments to held experts, and the largest held expert's load
    in the call (the same in every row).
    """
    B, S, d = x.shape
    N, K, El = B * S, cfg.experts_per_token, cfg.held_experts
    xf = x.reshape(N, d)
    with jax.named_scope("router"):
        _, top_w, top_e = moe_route(xf, p, cfg)                    # (N, K)
        gid = _held_id(top_e, cfg).reshape(N * K)
        order = jnp.argsort(gid, stable=True)
        held = gid[order] < El                                     # (N*K,)
        w = jnp.where(held, top_w.reshape(N * K)[order], 0.0)
        loads = jnp.zeros((El + 1,), jnp.int32).at[gid].add(1)[:El]
        xs = jnp.take(xf, order // K, axis=0)                      # (N*K, d)
    with jax.named_scope("experts"):
        wg, wu, wd = _expert_weights(p, cfg)
        sizes = loads
        if "layer" in p:
            # whole stacks (L, E, ...) as one group per layer and expert:
            # the other layers' groups are empty, so the grouped product
            # reads this layer's experts in place and no slice is copied
            n = wg.shape[0] * El
            wg, wu, wd = (a.reshape((n,) + a.shape[2:]) for a in (wg, wu, wd))
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n,), jnp.int32), loads, (p["layer"] * El,))
        h = jax.nn.silu(jax.lax.ragged_dot(xs, wg.astype(x.dtype), sizes)) \
            * jax.lax.ragged_dot(xs, wu.astype(x.dtype), sizes)
        ys = jax.lax.ragged_dot(h, wd.astype(x.dtype), sizes)      # (N*K, d)
    ys = jnp.where(held[:, None], ys.astype(jnp.float32) * w[:, None], 0.0)
    back = jnp.zeros((N * K,), jnp.int32).at[order].set(
        jnp.arange(N * K, dtype=jnp.int32))                        # inverse
    out = jnp.take(ys, back, axis=0).reshape(N, K, d).sum(1)
    out = out.astype(x.dtype).reshape(B, S, d)
    row_local = (gid < El).reshape(B, S * K).sum(-1, dtype=jnp.int32)
    stats = jnp.stack([row_local, jnp.broadcast_to(loads.max(), (B,))], -1)
    return shard_hint(out, "batch", "seq", None), stats


def moe_mlp(x: jax.Array, p: dict, cfg: ModelConfig, dropless: bool
            ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """An expert layer's MLP: the routed experts, dropless (serving) or by
    capacity with the aux loss (training), plus the shared experts, which
    every token passes through.  Returns (output, aux_loss, stats); stats
    as :func:`moe_dropless` gives them, None in training."""
    if dropless:
        out, stats = moe_dropless(x, p, cfg)
        aux = jnp.zeros((), jnp.float32)
    else:
        out, aux = moe_ffn(x, p, cfg)
        stats = None
    if cfg.n_shared_experts:
        with jax.named_scope("shared_experts"):
            out = out + swiglu_mlp(x, p["shared"])
    return out, aux, stats


# ------------------------------------------------------------- embeddings
def embed_tokens(tokens: jax.Array, table: jax.Array,
                 scale: bool = False) -> jax.Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(table.shape[-1] ** 0.5, x.dtype)
    return shard_hint(x, "batch", "seq", None)


def lm_logits(x: jax.Array, table: jax.Array,
              softcap: float = 0.0) -> jax.Array:
    logits = jnp.einsum("...d,vd->...v", x, table.astype(x.dtype))
    logits = _softcap(logits.astype(jnp.float32), softcap)
    return shard_hint(logits, "batch", None, "tp")
