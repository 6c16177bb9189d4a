"""The plain reference run over served requests, layer by layer.

For each request it takes the token ids the engine was fed (the prompt,
padded with 0 to the prefill bucket, then every served token but the last)
and runs the configuration's float32 reference over them, one layer at a
time: layer ``l``'s weights are drawn again from the seed (``weights``), in
float32, and dropped before the next, so a model whose float32 copy would
not fit beside anything else still runs.  The module's ``layer_weights``
says which group of stacked layers model layer ``l`` comes from; without
it every layer is drawn from ``blocks``.  It reads nothing the program
made.

At each position that produced a served token it reads the *gap*: the
reference's largest logit minus its logit of the served token.  A served
token the reference also puts first has gap 0; rounding in the served
precision can pick a near-tie, with a small gap; a wrong token has a gap
of the size of the logits' spread.  With ``controls`` the same layers
are run again with each control's ``quant`` (see ``references.common``),
and the gap of the token that the control puts first is read too.
"""
from __future__ import annotations

import importlib
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights
from .references.common import HI, linear, rms_norm

def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for k in path[1:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return out


def _uniform_layer(shapes: Dict[Tuple[str, ...], Tuple[int, ...]], seed: int,
                   c: dict, layer: int) -> Dict[Tuple[str, ...], jax.Array]:
    return weights.layer_weights(seed, shapes, c["torch_dtype"],
                                 c["num_hidden_layers"], layer)


def _whole_shapes(c: dict) -> Dict[Tuple[str, ...], Tuple[int, ...]]:
    d, V = c["hidden_size"], c["vocab_size"]
    shapes = {("embed",): (V, d), ("ln_f",): (d,)}
    if not c["tie_word_embeddings"]:
        shapes[("lm_head",)] = (V, d)
    return shapes


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, gain, table, rows, eps, quant):
    h = rms_norm(x[rows], gain, eps)
    if quant is None:
        return jnp.einsum("nd,vd->nv", h, table, precision=HI)
    return linear(h, table.T, quant=quant)


@jax.jit
def _gap(logits, tokens):
    return logits.max(-1) - jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]


def forward(c: dict, seed: int, seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
            quants: Sequence[Optional[str]] = (None,)):
    """Yield, for each sequence and each entry of ``quants``, the logits at
    ``rows`` of that sequence: ``out[q][j]`` is (len(rows[j]), vocab)."""
    mod = importlib.import_module(f".references.{c['reference']}", __package__)
    L, dtype = c["num_hidden_layers"], c["torch_dtype"]
    whole = weights.whole_weights(seed, _whole_shapes(c), dtype, L)
    table = whole[("lm_head",) if ("lm_head",) in whole else ("embed",)]
    blocks = {q: jax.jit(partial(mod.block, c=c, quant=q)) for q in quants}
    xs = {q: [whole[("embed",)][jnp.asarray(s)] for s in seqs] for q in quants}
    draw_layer = getattr(mod, "layer_weights", None) \
        or partial(_uniform_layer, mod.layer_shapes(c))
    for layer in range(L):
        w = _nest(draw_layer(seed, c, layer))
        for q in quants:
            xs[q] = [blocks[q](x, w) for x in xs[q]]
        del w
    return {q: [_head(x, whole[("ln_f",)], table, jnp.asarray(r),
                      eps=c["rms_norm_eps"], quant=q)
                for x, r in zip(xs[q], rows)]
            for q in quants}


def gaps(c: dict, seed: int, fed: Sequence[np.ndarray],
         served: Sequence[np.ndarray], first_row: int, length: int,
         controls: Sequence[str] = ()
         ) -> Tuple[List[np.ndarray], Dict[str, List[np.ndarray]]]:
    """Per request, the gap of each served token; and for each of
    ``controls``, the gap of each token that control puts first.

    ``fed[j]`` are the ids the engine was fed for request ``j`` (padded
    prompt then served tokens but the last), ``served[j]`` its served tokens;
    ``served[j][i]`` came from the logits at row ``first_row + i``.  Every
    sequence is padded to ``length`` with 0 after its end (the attention is
    causal, so padding behind a row does not change it), so one program
    serves them all."""
    seqs = [np.pad(np.asarray(f, np.int32), (0, length - len(f))) for f in fed]
    # every request reads the same number of rows (its last one repeated),
    # so each program compiles once per cell, whatever the answer lengths
    n_rows = length - first_row
    rows = [first_row + np.minimum(np.arange(n_rows), len(s) - 1)
            for s in served]
    out = forward(c, seed, seqs, rows, (None,) + tuple(controls))
    padded = [np.pad(np.asarray(s, np.int32), (0, n_rows - len(s)), "edge")
              for s in served]
    ref = [np.asarray(_gap(lg, jnp.asarray(s)))[:len(t)]
           for lg, s, t in zip(out[None], padded, served)]
    ctrl = {q: [np.asarray(_gap(lg, jnp.argmax(lq, -1)))[:len(t)]
                for lg, lq, t in zip(out[None], out[q], served)]
            for q in controls}
    return ref, ctrl
