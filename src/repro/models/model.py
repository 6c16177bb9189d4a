"""Model facade: one entry point over all six architecture families.

  Model(cfg).loss(params, batch)          — training objective
  Model(cfg).prefill(params, inputs)      — full-sequence forward + cache
  Model(cfg).decode_step(params, cache, tokens, pos)
  Model(cfg).input_specs(shape)           — ShapeDtypeStruct stand-ins for the
                                            multi-pod dry-run (no allocation)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import encdec, rglru, rwkv6, transformer
from .config import ModelConfig, ShapeConfig
from .init import abstract_params, count_params, init_params
from .kv_cache import init_cache
from .layers import lm_logits


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  z_loss: float = 1e-4) -> Tuple[jax.Array, Dict[str, Any]]:
    """Token-mean CE in fp32 with z-loss. logits: (B,S,V); labels: (B,S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = (lse - gold).mean()
    zl = z_loss * jnp.square(lse).mean()
    return ce + zl, {"ce": ce, "z_loss": zl}


class Model:
    def __init__(self, cfg: ModelConfig, use_kernels: bool = False) -> None:
        cfg.validate()
        self.cfg = cfg
        self.use_kernels = use_kernels

    # ------------------------------------------------------------ params
    def init(self, rng: jax.Array) -> Dict[str, Any]:
        return init_params(self.cfg, rng)

    def abstract_params(self) -> Dict[str, Any]:
        return abstract_params(self.cfg)

    def count_params(self) -> int:
        return count_params(self.cfg)

    def active_params(self) -> int:
        """Parameters a token passes through here.  In each expert layer a
        token picks ``experts_per_token`` of the ``n_experts`` routed
        experts, so of the experts held here it passes through
        ``experts_per_token * held / n_experts`` on average (rounded down
        in parameters); leading dense layers and shared experts count
        whole."""
        cfg = self.cfg
        total = self.count_params()
        if not cfg.n_experts:
            return total
        per_expert = 3 * cfg.d_model * cfg.d_ff
        held = cfg.held_experts
        active = per_expert * cfg.experts_per_token * held // cfg.n_experts
        n_moe = cfg.n_layers - cfg.n_dense_layers
        return total - n_moe * (held * per_expert - active)

    # ------------------------------------------------------------- train
    def loss(self, params: Dict[str, Any], batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, Any]]:
        cfg = self.cfg
        if cfg.family == "ssm":
            B = batch["tokens"].shape[0]
            state = init_cache(cfg, B, 0)
            x, _ = rwkv6.forward(params, cfg, batch, state,
                                 use_kernel=self.use_kernels,
                                 emit_state=False)
            logits = lm_logits(x, params["lm_head"], cfg.logit_softcap)
            aux = jnp.zeros((), jnp.float32)
        elif cfg.family == "hybrid":
            B = batch["tokens"].shape[0]
            cache = init_cache(cfg, B, 0)
            pos = jnp.zeros((B,), jnp.int32)
            x, _ = rglru.forward(params, cfg, batch, cache, decode=False,
                                 pos=pos, emit_cache=False)
            logits = lm_logits(x, params["lm_head"], cfg.logit_softcap)
            aux = jnp.zeros((), jnp.float32)
        elif cfg.is_encdec:
            x, aux = encdec.forward_train(params, cfg, batch)
            logits = lm_logits(x, params["lm_head"], cfg.logit_softcap)
        else:
            x, aux = transformer.forward(params, cfg, batch)
            logits = lm_logits(x, transformer._out_table(params, cfg),
                               cfg.logit_softcap)
        loss, metrics = cross_entropy(logits, batch["labels"])
        loss = loss + aux
        metrics["aux"] = aux
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------- serve
    def prefill(self, params: Dict[str, Any], inputs: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, Any]:
        """Returns (last-token logits (B,V), cache)."""
        cfg = self.cfg
        if cfg.family == "ssm":
            B = inputs["tokens"].shape[0]
            state = init_cache(cfg, B, 0)
            x, state = rwkv6.forward(params, cfg, inputs, state,
                                     use_kernel=self.use_kernels)
            logits = lm_logits(x[:, -1:], params["lm_head"],
                               cfg.logit_softcap)
            return logits[:, 0], state
        if cfg.family == "hybrid":
            B = inputs["tokens"].shape[0]
            cache = init_cache(cfg, B, 0)
            pos = jnp.zeros((B,), jnp.int32)
            x, cache = rglru.forward(params, cfg, inputs, cache,
                                     decode=False, pos=pos)
            logits = lm_logits(x[:, -1:], params["lm_head"],
                               cfg.logit_softcap)
            return logits[:, 0], cache
        if cfg.is_encdec:
            enc_out = encdec.encode(params, cfg, inputs["src"])
            x, cache = encdec.decode_fwd(params, cfg, inputs["tokens"],
                                         enc_out, emit_cache=True)
            logits = lm_logits(x[:, -1:], params["lm_head"],
                               cfg.logit_softcap)
            return logits[:, 0], cache
        return transformer.prefill(params, cfg, inputs)

    def decode_step(self, params: Dict[str, Any], cache: Any,
                    tokens: jax.Array, pos: jax.Array
                    ) -> Tuple[jax.Array, Any]:
        cfg = self.cfg
        if cfg.family == "ssm":
            return rwkv6.decode_step(params, cfg, cache, tokens, pos)
        if cfg.family == "hybrid":
            return rglru.decode_step(params, cfg, cache, tokens, pos)
        if cfg.is_encdec:
            return encdec.decode_step(params, cfg, cache, tokens, pos)
        return transformer.decode_step(params, cfg, cache, tokens, pos)

    def init_cache(self, batch: int, max_len: int,
                   abstract: bool = False) -> Any:
        return init_cache(self.cfg, batch, max_len, abstract)

    # ----------------------------------------------------------- dry-run
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this cell.

        train  -> kwargs for loss(params, batch)
        prefill-> kwargs for prefill(params, inputs)
        decode -> kwargs for decode_step(params, cache, tokens, pos)
        """
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sd = jax.ShapeDtypeStruct

        def lm_inputs(seq: int) -> Dict[str, Any]:
            d: Dict[str, Any] = {"tokens": sd((B, seq), i32)}
            if cfg.family == "vlm":
                d["embeds"] = sd((B, seq, cfg.d_model), cfg.cdtype)
                d["embed_mask"] = sd((B, seq), jnp.bool_)
                d["positions"] = sd((3, B, seq), i32)
            if cfg.is_encdec:
                # source frames at seq_len; target at seq_len // 4
                d = {"src": sd((B, seq, cfg.d_model), cfg.cdtype),
                     "tokens": sd((B, max(seq // 4, 8)), i32)}
            return d

        if shape.kind == "train":
            batch = lm_inputs(S)
            tgt = batch["tokens"].shape
            batch["labels"] = sd(tgt, i32)
            return {"batch": batch}
        if shape.kind == "prefill":
            return {"inputs": lm_inputs(S)}
        # decode: one new token against a cache of S
        cache = self.init_cache(B, S, abstract=True)
        return {"cache": cache,
                "tokens": sd((B, 1), i32),
                "pos": sd((B,), i32)}

    def step_fn(self, kind: str):
        """The jittable callable for a given shape kind (serve side)."""
        if kind == "prefill":
            return lambda params, inputs: self.prefill(params, inputs)
        if kind == "decode":
            return lambda params, cache, tokens, pos: \
                self.decode_step(params, cache, tokens, pos)
        raise ValueError(kind)
