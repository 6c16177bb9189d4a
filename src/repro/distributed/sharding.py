"""Parameter / input / cache sharding assignment.

Walks a params pytree and assigns each leaf a logical-axis tuple by leaf
name (Megatron column/row-parallel + FSDP), then resolves it against the
mesh through :class:`ShardCtx` (divisibility auto-degrade, axis-used-once).

Key behaviors falling out of the rule engine, per arch:
  * olmoe  (64 experts): expert dim takes "model" -> expert parallelism
  * grok-1 (8 experts < 16): expert dim degrades, d_ff takes "model"
    -> tensor parallelism *inside* each expert
  * qwen2-0.5b (14 heads): attention weight TP degrades on the merged head
    dim only if 896 % 16 != 0 (it is divisible: 56/chip) — activations
    degrade instead (see models/layers.py shard hints)
  * llama3-405b decode: kv_heads (8) % 16 != 0 -> KV cache shards its
    *sequence* dim over "model" (flash-decode layout)
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding

from ..models import Model
from ..models.config import ModelConfig, ShapeConfig
from .api import ShardCtx

# Megatron column-parallel (input dim -> FSDP, output dim -> TP)
_COL = {"wq", "wk", "wv", "wg", "wr", "wq_a", "wq_b", "wkv_a", "wkv_b",
        "w_gate", "w_up", "w_k", "tm_w1", "decay_w1", "w_y", "w_x"}
# Megatron row-parallel (input dim -> TP, output dim -> FSDP)
_ROW = {"wo", "w_down", "w_v", "decay_w2", "w_o"}
_BIAS_TP = {"bq", "bk", "bv", "conv_b", "lam", "gate_a_b", "gate_i_b"}


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", str(last))


def logical_axes_for(path, shape: Sequence[int]
                     ) -> Tuple[Optional[str], ...]:
    """Logical axis names for one parameter leaf."""
    name = _leaf_name(path)
    nd = len(shape)
    if name in ("embed", "lm_head"):
        return ("wtp", "fsdp")
    if name == "router":                      # (L, d, E): keep E whole
        return (None, "fsdp", None)
    if name in _COL:
        if nd == 4:                           # MoE expert (L, E, d, f)
            return (None, "experts", "fsdp", "wtp")
        if nd == 3:                           # (L, d, out)
            return (None, "fsdp", "wtp")
    if name in _ROW:
        if nd == 4:                           # MoE expert (L, E, f, d)
            return (None, "experts", "wtp", "fsdp")
        if nd == 3:                           # (L, in, d)
            return (None, "wtp", "fsdp")
    if name in _BIAS_TP and nd == 2:          # (L, out)
        return (None, "wtp")
    if name in ("gate_a_w", "gate_i_w") and nd == 4:  # (L, nb, bw, bw)
        return (None, "wtp", None, None)
    if name == "conv_w" and nd == 3:          # (L, cw, W)
        return (None, None, "wtp")
    if name == "tm_w2" and nd == 4:           # (L, 5, lora, d)
        return (None, None, None, "fsdp")
    # norms, scalars, token-shift mus, u, w0: replicated
    return (None,) * nd


def param_shardings(ctx: ShardCtx, params_abstract: Any) -> Any:
    """NamedSharding pytree matching ``params_abstract``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_abstract)
    out = []
    for path, leaf in flat:
        names = logical_axes_for(path, leaf.shape)
        out.append(ctx.sharding(names, leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def opt_state_shardings(ctx: ShardCtx, opt_abstract: Any,
                        param_shards: Any) -> Any:
    """m/v follow the params; step is replicated."""
    return {
        "m": jax.tree.map(lambda s, l: s, param_shards, opt_abstract["m"]),
        "v": jax.tree.map(lambda s, l: s, param_shards, opt_abstract["v"]),
        "step": ctx.sharding((), ()),
    }


# ------------------------------------------------------------------ inputs
def batch_shardings(ctx: ShardCtx, batch_abstract: Any) -> Any:
    """Training/prefill inputs: batch over ("pod","data"), rest replicated.
    The M-RoPE positions tensor (3,B,S) carries batch in dim 1."""
    def assign(path, leaf):
        name = _leaf_name(path)
        if name == "positions" and len(leaf.shape) == 3 \
                and leaf.shape[0] == 3:
            return ctx.sharding((None, "batch", None), leaf.shape)
        names = ("batch",) + (None,) * (len(leaf.shape) - 1)
        return ctx.sharding(names, leaf.shape)

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_abstract)
    return jax.tree_util.tree_unflatten(
        treedef, [assign(p, l) for p, l in flat])


def cache_shardings(ctx: ShardCtx, cfg: ModelConfig,
                    cache_abstract: Any) -> Any:
    """Decode caches: batch on ("pod","data"); per-head TP when the kv-head
    count divides the model axis, else sequence-sharded KV (flash-decode)."""
    tp = ctx.mesh.shape.get("model", 1)
    heads_divide = cfg.n_kv_heads > 0 and cfg.n_kv_heads % tp == 0

    def assign(path, leaf):
        name = _leaf_name(path)
        nd = len(leaf.shape)
        if name in ("k", "v", "cross_k", "cross_v") and nd == 5:
            if heads_divide:
                names = (None, "batch", None, "tp", None)
            else:
                names = (None, "batch", "kv_seq", None, None)
        elif name in ("k", "v") and nd == 4:          # (L,B,S,Hkv*D)
            if heads_divide:                          # whole heads per shard
                names = (None, "batch", None, "tp")
            else:
                names = (None, "batch", "kv_seq", None)
        elif name in ("latent", "k_rope") and nd == 4:   # MLA (L,B,S,r|dr)
            names = (None, "batch", "kv_seq", None)
        elif name == "wkv" and nd == 5:               # rwkv (L,B,H,D,D)
            names = (None, "batch", "tp", None, None)
        elif name == "h" and nd == 3:                 # lru (Lr,B,W)
            names = (None, "batch", "tp")
        elif name == "conv" and nd == 4:              # (Lr,B,cw-1,W)
            names = (None, "batch", None, "tp")
        elif nd >= 2:
            names = (None, "batch") + (None,) * (nd - 2)
        else:
            names = (None,) * nd
        return ctx.sharding(names, leaf.shape)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_abstract)
    return jax.tree_util.tree_unflatten(
        treedef, [assign(p, l) for p, l in flat])


def step_in_shardings(ctx: ShardCtx, model: Model, shape: ShapeConfig,
                      specs: Any) -> Any:
    """in_shardings pytree matching Model.input_specs(shape) kwargs."""
    cfg = model.cfg
    if shape.kind == "train":
        return {"batch": batch_shardings(ctx, specs["batch"])}
    if shape.kind == "prefill":
        return {"inputs": batch_shardings(ctx, specs["inputs"])}
    return {
        "cache": cache_shardings(ctx, cfg, specs["cache"]),
        "tokens": ctx.sharding(("batch", None), specs["tokens"].shape),
        "pos": ctx.sharding(("batch",), specs["pos"].shape),
    }
