"""Seeded weights: the whole tree on the device in one jitted call, and one
layer again, alone, for the reference.

A leaf ``path`` whose top-level key is ``blocks`` or ends in ``_blocks`` (a
group of stacked layers, such as ``dense_blocks``) has the shape ``(n, ...)``
and is drawn layer by layer: layer ``i`` of the group from
``fold_in(fold_in(seed_key, crc32(path)), i)``, the group's name in ``path``.
So the reference can draw one layer by itself and get the very values
served, without reading anything the program holds.  ``n_layers``, which
scales the output projections, is the model's total over all groups.
Values are drawn in float32 and rounded to the served type; the reference
takes that rounding back to float32.

Scales follow ``repro.models.init`` (``std`` 0.02, output projections
``0.02 / sqrt(2 L)``) with three departures, each so that the comparison
sees more:

* query and key projections (``wq``, ``wk``; MLA's query up-projection
  ``wq_b``) have ``std`` 0.045.  At 0.02 attention is nearly uniform over
  the context, every position's hidden state is nearly the same, and a
  published-width model answers every prompt with one token repeated (seen
  at qwen2-0.5b's widths): a decode step that dropped its cache would serve
  the same tokens.  At 0.1 qwen2-0.5b's attention scores have a spread of
  about 9, attention picks one key, and bfloat16 rounding flips which one:
  on a TPU v5e the served tokens then lay up to 3-4 logits below the
  float32 reference's best, as far as the fp8 control's and a broken decode
  step's (PERF.md).  At 0.045 the scores spread by about 2, a cache fault
  still reads 2.8 or more, and sound runs read under 0.1;
* biases are drawn (std 0.02) instead of zero, and norm gains are
  ``1 + N(0, 0.1)`` instead of one, so that a bias or gain left out changes
  the answer.
"""
from __future__ import annotations

import zlib
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

STD = 0.02
QK_STD = 0.045
BIAS_STD = 0.02
GAIN_STD = 0.1
NORMS = frozenset({"ln1", "ln2", "ln_f", "q_norm", "k_norm", "kv_norm"})
BIASES = frozenset({"bq", "bk", "bv"})
OUTPUTS = frozenset({"wo", "w_down"})
QUERY_KEY = frozenset({"wq", "wk", "wq_b"})

Path = Tuple[str, ...]


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_key(key: jax.Array, path: Path) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))


def draw(key: jax.Array, path: Path, shape: Tuple[int, ...],
         n_layers: int) -> jax.Array:
    """One leaf (or one layer of a stacked leaf) in float32."""
    name = path[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        return 1.0 + GAIN_STD * z
    if name in BIASES:
        return BIAS_STD * z
    if name in OUTPUTS:
        return STD / (2 * n_layers) ** 0.5 * z
    if name in QUERY_KEY:
        return QK_STD * z
    return STD * z


def _spec(abstract: Any) -> List[Tuple[Path, Tuple[int, ...], Any]]:
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    return [(tuple(k.key for k in kp), tuple(leaf.shape), leaf.dtype)
            for kp, leaf in flat]


def _make(spec, treedef, n_layers: int, key: jax.Array) -> Any:
    leaves = []
    for path, shape, dtype in spec:
        k = leaf_key(key, path)
        if path[0] == "blocks" or path[0].endswith("_blocks"):
            leaf = jax.lax.map(
                lambda l, k=k, path=path, shape=shape, dtype=dtype: draw(
                    jax.random.fold_in(k, l), path, shape[1:],
                    n_layers).astype(dtype),
                jnp.arange(shape[0], dtype=jnp.uint32))
        else:
            leaf = draw(k, path, shape, n_layers).astype(dtype)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_params(abstract: Any, n_layers: int, seed: int) -> Any:
    """The served weights for ``seed``, shaped and typed as ``abstract``
    (``jax.eval_shape`` of the model's init), made in one jitted call."""
    spec = _spec(abstract)
    treedef = jax.tree_util.tree_structure(abstract)
    fn = jax.jit(partial(_make, spec, treedef, n_layers))
    return jax.block_until_ready(fn(seed_key(seed)))


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _layer_leaf(key, path, shape, dtype, n_layers, layer):
    k = jax.random.fold_in(leaf_key(key, path), layer)
    return draw(k, path, shape, n_layers).astype(dtype).astype(jnp.float32)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _whole_leaf(key, path, shape, dtype, n_layers):
    return draw(leaf_key(key, path), path, shape,
                n_layers).astype(dtype).astype(jnp.float32)


def layer_weights(seed: int, shapes: Dict[Path, Tuple[int, ...]],
                  dtype: Any, n_layers: int, layer: int
                  ) -> Dict[Path, jax.Array]:
    """Layer ``layer`` of each stacked leaf in ``shapes`` (path -> shape of
    one layer), in float32, as served in ``dtype``; ``layer`` counts within
    the leaf's group."""
    key = seed_key(seed)
    lay = jnp.asarray(layer, jnp.uint32)
    return {p: _layer_leaf(key, p, s, jnp.dtype(dtype), n_layers, lay)
            for p, s in shapes.items()}


def whole_weights(seed: int, shapes: Dict[Path, Tuple[int, ...]],
                  dtype: Any, n_layers: int) -> Dict[Path, jax.Array]:
    """Unstacked leaves (embedding, final norm, head) in float32."""
    key = seed_key(seed)
    return {p: _whole_leaf(key, p, s, jnp.dtype(dtype), n_layers)
            for p, s in shapes.items()}
