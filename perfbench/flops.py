"""Operations and bytes the served algorithm needs, from a configuration's
published sizes.  Independent of how the program implements them.

Each reference module (``references/<reference>.py``) counts its own kind
through ``counts(c)``; ``counts`` here finds the module by the
configuration's ``reference`` key.  The metric readers call only three
methods of what it returns: ``prefill_flops(n)``, ``decode_flops(active,
ctx)`` and ``decode_bytes(active, ctx)``.  A module whose step does not read
every weight once (an expert layer reads the experts its tokens pick) may
return its own subclass of ``Counts`` that overrides them.

For ``Counts`` as it stands:

* A token through the model costs 2 FLOPs per weight of every projection
  (attention, MLP, output head; MLA's latent up-projection counted per
  token, as in its expanded form) plus the attention itself: per layer and
  per context position, 2 FLOPs per query-key channel and 2 per value
  channel of each head.
* A decode step must read every weight once, each active slot's cache up
  to and including its own position, write the new cache entries, and
  write the active rows' float32 logits.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
LOGIT_BYTES = 4


@dataclass(frozen=True)
class Counts:
    matmul_weights: int        # weights a token passes through, head included
    attn_flops_per_ctx: int    # attention FLOPs per token per context position
    weight_bytes: int          # bytes a decode step must read once
    kv_bytes_per_token: int    # cache bytes one position holds
    embed_row_bytes: int       # one embedding row, read per decoded token
    vocab: int

    def prefill_flops(self, n: int) -> float:
        """A prompt of ``n`` real tokens, causal."""
        return 2.0 * self.matmul_weights * n \
            + self.attn_flops_per_ctx * n * (n + 1) / 2

    def decode_flops(self, active: int, ctx: int) -> float:
        """One step of ``active`` slots whose context lengths (position of
        the new token + 1) sum to ``ctx``."""
        return 2.0 * self.matmul_weights * active \
            + float(self.attn_flops_per_ctx) * ctx

    def decode_bytes(self, active: int, ctx: int) -> float:
        return float(self.weight_bytes) + self.kv_bytes_per_token * ctx \
            + active * (self.embed_row_bytes + self.vocab * LOGIT_BYTES)


def stack_counts(c: dict, proj: int, small: int, attn: int, kv: int
                 ) -> Counts:
    """A stack of ``num_hidden_layers`` alike layers, each an attention
    block with ``proj`` projection weights, ``small`` norm gains and biases,
    ``attn`` FLOPs per context position and ``kv`` cache elements per
    position, and a SwiGLU MLP of ``intermediate_size``; under an embedding
    and a head of ``vocab_size`` rows and a final norm."""
    d, f, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    V, b = c["vocab_size"], _BYTES[c["torch_dtype"]]
    layer = proj + 3 * d * f
    head = V * d
    return Counts(matmul_weights=L * layer + head,
                  attn_flops_per_ctx=L * attn,
                  weight_bytes=(L * (layer + small) + head + d) * b,
                  kv_bytes_per_token=L * kv * b,
                  embed_row_bytes=d * b,
                  vocab=V)


def counts(c: dict) -> Counts:
    mod = importlib.import_module(f".references.{c['reference']}", __package__)
    return mod.counts(c)
