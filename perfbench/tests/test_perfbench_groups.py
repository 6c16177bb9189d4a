"""Room for a configuration of a new kind, as new files alone: weights in
more than one group of stacked layers, a reference module that walks its
own layer plan and counts its own FLOPs, per-layer metrics that apply to
every cell without a list.  On the CPU at toy size."""
import hashlib
import json
import sys
import types
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import flops, harness, reference, weights
from perfbench.harness import HERE, ROOT
from perfbench.references.common import HI, linear, rms_norm, swiglu
from perfbench.tests.smoke import SMOKE_CONFIGS
from perfbench.tests.test_perfbench_reference import SEED, model_of

D, F, E, V, L = 16, 24, 4, 32, 4
GROUPS = {"dense_blocks": 1, "blocks": L - 1}     # one dense layer, then 3
TOY = {"reference": "toy", "torch_dtype": "bfloat16", "hidden_size": D,
       "vocab_size": V, "num_hidden_layers": L, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": True}


# ----------------------------------------------- the toy reference module
def toy_layer_shapes(c):
    return {("dense_blocks", "ln1"): (D,),
            ("dense_blocks", "mlp", "w_gate"): (D, F),
            ("dense_blocks", "mlp", "w_up"): (D, F),
            ("dense_blocks", "mlp", "w_down"): (F, D),
            ("blocks", "ln1"): (D,),
            ("blocks", "moe", "router"): (D, E),
            ("blocks", "moe", "w_up"): (E, D, F),
            ("blocks", "moe", "w_down"): (E, F, D)}


def group_of(layer):
    return ("dense_blocks", layer) if layer < 1 else ("blocks", layer - 1)


def toy_layer_weights(seed, c, layer):
    group, i = group_of(layer)
    shapes = {p: s for p, s in toy_layer_shapes(c).items() if p[0] == group}
    return weights.layer_weights(seed, shapes, c["torch_dtype"],
                                 c["num_hidden_layers"], i)


def toy_block(x, w, c, quant):
    """A dense SwiGLU layer, or a soft mixture of ``E`` experts: told apart
    by the keys of ``w``."""
    h = rms_norm(x, w["ln1"], c["rms_norm_eps"])
    if "mlp" in w:
        return x + swiglu(h, w["mlp"], quant)
    m = w["moe"]
    gate = jax.nn.softmax(linear(h, m["router"], quant=quant), -1)
    up = jax.nn.gelu(jnp.einsum("td,edf->tef", h, m["w_up"], precision=HI))
    out = jnp.einsum("tef,efd->ted", up, m["w_down"], precision=HI)
    return x + jnp.einsum("te,ted->td", gate, out, precision=HI)


@dataclass(frozen=True)
class ToyCounts(flops.Counts):
    """An expert layer's step reads only the experts picked, not every
    weight once."""
    expert_bytes: int = 0

    def decode_bytes(self, active, ctx):
        return float(self.expert_bytes * active) + self.kv_bytes_per_token * ctx


def toy_counts(c):
    return ToyCounts(matmul_weights=1, attn_flops_per_ctx=2, weight_bytes=3,
                     kv_bytes_per_token=4, embed_row_bytes=5, vocab=V,
                     expert_bytes=7)


@pytest.fixture
def toy(monkeypatch):
    mod = types.ModuleType("perfbench.references.toy")
    mod.layer_shapes, mod.block = toy_layer_shapes, toy_block
    mod.counts, mod.layer_weights = toy_counts, toy_layer_weights
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def toy_params():
    """The served tree: each group's leaves stacked over its layers."""
    bf = jnp.bfloat16
    tree = {"embed": jax.ShapeDtypeStruct((V, D), bf),
            "ln_f": jax.ShapeDtypeStruct((D,), bf)}
    for path, shape in toy_layer_shapes(TOY).items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = jax.ShapeDtypeStruct((GROUPS[path[0]],) + shape, bf)
    return weights.make_params(tree, L, SEED)


def leaf(tree, path):
    for k in path:
        tree = tree[k]
    return tree


# ----------------------------------------------------------------- tests
def test_each_layer_group_is_drawn_layer_by_layer():
    params = toy_params()
    for group, n in GROUPS.items():
        shapes = {p: s for p, s in toy_layer_shapes(TOY).items()
                  if p[0] == group}
        for i in range(n):
            drawn = weights.layer_weights(SEED, shapes, "bfloat16", L, i)
            for path, value in drawn.items():
                np.testing.assert_array_equal(
                    np.asarray(leaf(params, path)[i], np.float32),
                    np.asarray(value), err_msg=f"{path}[{i}]")
    # the group's name is part of the leaf's key
    assert not np.array_equal(np.asarray(params["dense_blocks"]["ln1"][0]),
                              np.asarray(params["blocks"]["ln1"][0]))


def test_reference_walks_the_modules_layer_plan(toy):
    params = toy_params()
    T = 12
    seq = np.random.default_rng(3).integers(0, V, T).astype(np.int32)
    rows = np.arange(4, T)
    got = reference.forward(TOY, SEED, [seq], [rows])[None][0]

    f32 = lambda a: jnp.asarray(a, jnp.float32)          # noqa: E731
    embed = f32(params["embed"])
    x = embed[seq]
    for layer in range(L):
        group, i = group_of(layer)
        w = jax.tree.map(lambda a, i=i: f32(a[i]), params[group])
        x = toy_block(x, w, TOY, None)
    h = rms_norm(x[rows], f32(params["ln_f"]), TOY["rms_norm_eps"])
    want = jnp.einsum("nd,vd->nv", h, embed, precision=HI)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_counts_come_from_the_reference_module(toy):
    got = flops.counts(TOY)
    assert type(got) is ToyCounts and got == toy_counts(TOY)
    assert got.decode_bytes(2, 10) == 2 * 7 + 4 * 10


DIGESTS = json.loads((HERE / "tests" / "smoke_weight_digests.json").read_text())


@pytest.mark.parametrize("name", sorted(SMOKE_CONFIGS))
def test_smoke_weights_are_bit_for_bit_as_before(name):
    """Digests of every served leaf, taken before stacked groups other than
    ``blocks`` were drawn layer by layer."""
    c = SMOKE_CONFIGS[name]
    model = model_of(c, "bfloat16")
    params = weights.make_params(jax.eval_shape(model.init, jax.random.key(0)),
                                 model.cfg.n_layers, SEED)
    got = {}
    for kp, value in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(value)
        h = hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes())
        got["/".join(k.key for k in kp)] = h.hexdigest()[:16]
    assert got == DIGESTS[name]


def test_metrics_without_a_list_apply_to_every_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    everywhere = [m for m in bench["per_layer"] if "workloads" not in m]
    assert {"engine.batch_occupancy", "step.decode_ms", "step.decode_roofline",
            "device.mfu", "device.idle_share"} <= {m["name"] for m in everywhere}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        per_layer = {m["name"] for m in cell.metrics["per_layer"]}
        end_to_end = {m["name"] for m in cell.metrics["end_to_end"]}
        for m in everywhere:
            assert m["name"] in per_layer, (w["name"], m["name"])
            assert m["moves"] in end_to_end, (w["name"], m["name"])
