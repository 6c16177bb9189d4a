"""DeepSeek-V2 decoder layer: multi-head latent attention without a query
low-rank path, YaRN-scaled rotary channels, and either a dense SwiGLU (the
leading ``first_k_dense_replace`` layers) or routed experts beside shared
ones.

Follows the published description (arXiv:2405.04434; HF
``DeepseekV2Model`` of hf:deepseek-ai/DeepSeek-V2-Lite): queries ``x @ wq``
(``q_lora_rank`` null); keys and values from one compressed latent
(RMSNorm'd), expanded here by ``wkv_b`` to ``qk_nope_head_dim`` key and
``v_head_dim`` value channels per head, so this is independent of the
served, weight-absorbed form; a rotary key of ``qk_rope_head_dim``
channels shared by all heads; YaRN frequencies and the softmax scale times
``mscale(factor, mscale_all_dim)^2``.  An expert layer routes each token by
a softmax over all ``deployment.n_routed_experts`` experts, keeps the
greedy top ``num_experts_per_tok`` (renormalised only where
``norm_topk_prob``), and adds the weighted SwiGLU of each picked expert
that this chip holds (``n_routed_experts`` of them from
``deployment.first_held_expert`` on; the others lie on other chips and
add nothing here), plus the shared experts' SwiGLU of
``n_shared_experts * moe_intermediate_size``.  Each expert is computed on
every token and weighted by a dense combine matrix, which is zero where
the token did not pick it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import weights
from ..flops import _BYTES, Counts
from .common import HI, linear, rms_norm, swiglu

Shapes = Dict[Tuple[str, ...], Tuple[int, ...]]


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(c: dict) -> Tuple[jax.Array, float]:
    """The rotary channels' inverse frequencies and the cos/sin factor."""
    rs, d, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    f = rs["factor"]
    extra = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def dim(rot: float) -> float:     # channel that turns ``rot`` times
        return d * math.log(rs["original_max_position_embeddings"]
                            / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim(rs["beta_fast"])), 0)
    high = min(math.ceil(dim(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    inv = extra / f * ramp + extra * (1 - ramp)
    return inv, _mscale(f, rs["mscale"]) / _mscale(f, rs["mscale_all_dim"])


def rope(x: jax.Array, positions: jax.Array, c: dict) -> jax.Array:
    """YaRN rotary embedding, rotate-half layout. x: (T, H, D)."""
    inv, scale = yarn(c)
    ang = positions[:, None].astype(jnp.float32) * inv          # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    d = x.shape[-1]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def layer_shapes(c: dict) -> Shapes:
    d, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r, f, F = c["kv_lora_rank"], c["moe_intermediate_size"], \
        c["intermediate_size"]
    E, El = c["deployment"]["n_routed_experts"], c["n_routed_experts"]
    fs = c["n_shared_experts"] * f
    out: Shapes = {}
    for g in ("dense_blocks", "blocks"):
        out.update({
            (g, "ln1"): (d,), (g, "ln2"): (d,),
            (g, "attn", "wq"): (d, H * (dn + dr)),
            (g, "attn", "wkv_a"): (d, r + dr),
            (g, "attn", "kv_norm"): (r,),
            (g, "attn", "wkv_b"): (r, H * (dn + dv)),
            (g, "attn", "wo"): (H * dv, d)})
    out.update({
        ("dense_blocks", "mlp", "w_gate"): (d, F),
        ("dense_blocks", "mlp", "w_up"): (d, F),
        ("dense_blocks", "mlp", "w_down"): (F, d),
        ("blocks", "mlp", "router"): (d, E),
        ("blocks", "mlp", "w_gate"): (El, d, f),
        ("blocks", "mlp", "w_up"): (El, d, f),
        ("blocks", "mlp", "w_down"): (El, f, d),
        ("blocks", "mlp", "shared", "w_gate"): (d, fs),
        ("blocks", "mlp", "shared", "w_up"): (d, fs),
        ("blocks", "mlp", "shared", "w_down"): (fs, d)})
    return out


def layer_weights(seed: int, c: dict, layer: int
                  ) -> Dict[Tuple[str, ...], jax.Array]:
    """Model layer ``layer``: ``dense_blocks[layer]`` for the leading dense
    layers, else ``blocks[layer - first_k_dense_replace]``.  The router is
    drawn in float32, as the program keeps it."""
    nd = c["first_k_dense_replace"]
    group, i = ("dense_blocks", layer) if layer < nd else ("blocks", layer - nd)
    shapes = {p: s for p, s in layer_shapes(c).items() if p[0] == group}
    L = c["num_hidden_layers"]
    out = {}
    for dtype, part in (("float32", lambda p: p[-1] == "router"),
                        (c["torch_dtype"], lambda p: p[-1] != "router")):
        sub = {p: s for p, s in shapes.items() if part(p)}
        if sub:
            out.update(weights.layer_weights(seed, sub, dtype, L, i))
    return out


def attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float
              ) -> jax.Array:
    """Causal softmax attention. q, k: (T, H, Dk), v: (T, H, Dv)."""
    T = q.shape[0]
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HI)


def experts(h: jax.Array, m: dict, c: dict, quant: Optional[str]
            ) -> jax.Array:
    """The held routed experts' weighted sum over tokens h: (T, d)."""
    E, K = c["deployment"]["n_routed_experts"], c["num_experts_per_tok"]
    first, El = c["deployment"]["first_held_expert"], c["n_routed_experts"]
    probs = jax.nn.softmax(linear(h, m["router"], quant=quant), -1)
    top_w, top_e = jax.lax.top_k(probs, K)                      # (T, K)
    if c["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    else:
        top_w = top_w * c["routed_scaling_factor"]
    held = first + jnp.arange(El)
    combine = (top_w[:, :, None] * (top_e[:, :, None] == held)).sum(1)
    ys = jax.vmap(lambda g, u, dn: swiglu(
        h, {"w_gate": g, "w_up": u, "w_down": dn}, quant))(
        m["w_gate"], m["w_up"], m["w_down"])                   # (El, T, d)
    return jnp.einsum("te,etd->td", combine, ys, precision=HI)


def block(x: jax.Array, w: dict, c: dict, quant: Optional[str]) -> jax.Array:
    """One layer over one sequence. x: (T, d) float32."""
    T = x.shape[0]
    H = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r, eps = c["kv_lora_rank"], c["rms_norm_eps"]
    pos = jnp.arange(T)
    a = w["attn"]
    h = rms_norm(x, w["ln1"], eps)
    q = linear(h, a["wq"], quant=quant).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, c)], -1)
    kv_a = linear(h, a["wkv_a"], quant=quant)
    latent = rms_norm(kv_a[:, :r], a["kv_norm"], eps)
    k_rope = rope(kv_a[:, None, r:], pos, c)                    # (T, 1, dr)
    kv = linear(latent, a["wkv_b"], quant=quant).reshape(T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (T, H, dr))], -1)
    o = attention(q, k, kv[..., dn:], softmax_scale(c)).reshape(T, H * dv)
    x = x + linear(o, a["wo"], quant=quant)
    h = rms_norm(x, w["ln2"], eps)
    m = w["mlp"]
    if "router" not in m:
        return x + swiglu(h, m, quant)
    return x + experts(h, m, c, quant) + swiglu(h, m["shared"], quant)


@dataclass(frozen=True)
class ExpertCounts(Counts):
    """``Counts`` for a model whose expert layers hold ``held`` of ``routed``
    routed experts, each of ``expert_weights`` weights, on this chip.

    * A token passes through every weight but the routed experts' once
      (attention, dense layers, shared experts, router, head), and through
      ``top_k * held / routed`` held experts on average (1.5 at 6 of 64,
      16 held): ``matmul_weights`` counts both, so ``prefill_flops`` and
      ``decode_flops`` are the base class's formulas.
    * A decode step reads every weight but the routed experts' once
      (``weight_bytes``), the cache and logits as the base class says, and
      each held expert that some token of the step picked: under uniform
      routing one token leaves a given expert out with probability
      ``(routed - top_k) / routed``, so ``active`` tokens touch
      ``held * (1 - ((routed - top_k) / routed) ** active)`` of them in
      each expert layer (``expert_bytes``).
    * ``expert_flops``: the held experts' products of a step,
      ``2 * active * expert_layers * (top_k * held / routed) *
      expert_weights``.
    """
    expert_weights: int = 0     # one routed expert: gate, up, down
    expert_layers: int = 0
    held: int = 0
    routed: int = 0
    top_k: int = 0
    dtype_bytes: int = 2

    def expert_flops(self, active: int) -> float:
        return 2.0 * active * self.expert_layers * self.top_k * self.held \
            / self.routed * self.expert_weights

    def expert_bytes(self, active: int) -> float:
        miss = ((self.routed - self.top_k) / self.routed) ** active
        return self.expert_layers * self.held * (1.0 - miss) \
            * self.expert_weights * self.dtype_bytes

    def decode_bytes(self, active: int, ctx: int) -> float:
        return super().decode_bytes(active, ctx) + self.expert_bytes(active)


def counts(c: dict) -> ExpertCounts:
    d, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r, f, F = c["kv_lora_rank"], c["moe_intermediate_size"], \
        c["intermediate_size"]
    L, nd = c["num_hidden_layers"], c["first_k_dense_replace"]
    E, El, K = c["deployment"]["n_routed_experts"], c["n_routed_experts"], \
        c["num_experts_per_tok"]
    b = _BYTES[c["torch_dtype"]]
    nm = L - nd
    attn = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    expert = 3 * d * f
    shared = c["n_shared_experts"] * expert
    router = d * E
    dense = L * attn + nd * 3 * d * F + nm * (shared + router) + V * d
    small = L * (2 * d + r) + d                      # norm gains, ln_f
    return ExpertCounts(
        matmul_weights=dense + nm * K * El * expert // E,
        attn_flops_per_ctx=L * (2 * H * (dn + dr) + 2 * H * dv),
        # the router is kept in float32
        weight_bytes=(dense - nm * router + small) * b + nm * router * 4,
        kv_bytes_per_token=L * (r + dr) * b,
        embed_row_bytes=d * b,
        vocab=V,
        expert_weights=expert, expert_layers=nm, held=El, routed=E, top_k=K,
        dtype_bytes=b)
