"""DeepSeek-V2-Lite's pieces in the program, on the CPU at smoke size: YaRN,
MLA without q_lora, the dropless expert layer that is told which experts it
holds, the leading dense layer, and the served path through the engine.
The comparison with the plain float32 reference is in
``perfbench/tests/test_perfbench_deepseek_v2.py``."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import Model
from repro.models.layers import (moe_dropless, moe_ffn, moe_mlp, swiglu_mlp,
                                 yarn_rope)
from repro.serving import ServeConfig, build_llm_app
from repro.serving.engine import InferenceEngine
from repro.serving.spans import SpanRecorder
from test_chip_smoke import chip_smoke

ARCH = "deepseek-v2-lite"


def _f32(cfg):
    return cfg.with_(param_dtype="float32", compute_dtype="float32")


def _moe_params(cfg, rng, scale=1.0):
    """One expert layer's weights for ``cfg``: the held experts only."""
    d, f, E, El = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.held_experts
    fs = cfg.n_shared_experts * f
    k = jax.random.split(jax.random.key(rng), 7)
    n = lambda i, s: jax.random.normal(k[i], s, jnp.float32) * 0.2  # noqa
    return {"router": n(0, (d, E)) * scale,
            "w_gate": n(1, (El, d, f)), "w_up": n(2, (El, d, f)),
            "w_down": n(3, (El, f, d)),
            "shared": {"w_gate": n(4, (d, fs)), "w_up": n(5, (d, fs)),
                       "w_down": n(6, (fs, d))}}


def _naive_experts(x, p, cfg):
    """Every token through every held expert, weighted by its gate: the
    layer's definition with nothing dropped (float32)."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    top_w, top_e = jax.lax.top_k(jax.nn.softmax(logits, -1),
                                 cfg.experts_per_token)
    if cfg.moe_norm_topk:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for j in range(cfg.held_experts):
        e = cfg.first_held_expert + j
        w = (top_w * (top_e == e)).sum(-1)[..., None]
        h = jax.nn.silu(x @ p["w_gate"][j]) * (x @ p["w_up"][j])
        out = out + w * (h @ p["w_down"][j])
    return out


def test_preset_holds_16_of_64_experts_at_published_widths():
    cfg = get_config(ARCH)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab_size) == \
        (27, 2048, 16, 102400)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (0, 512, 128, 64, 128)
    assert (cfg.n_dense_layers, cfg.dense_d_ff, cfg.d_ff) == (1, 10944, 1408)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.n_shared_experts,
            cfg.held_experts, cfg.first_held_expert) == (64, 6, 2, 16, 0)
    assert not cfg.moe_norm_topk and not cfg.tie_embeddings


def test_parameters_counted_by_hand():
    """Attention 13.76 M a layer, a held expert 8.65 M, shared experts
    17.3 M, the router 0.13 M, the dense MLP 67.2 M, embedding and head
    419.4 M: 4.91 B held; a token passes 1.5 held experts a layer."""
    d, H, r, dr = 2048, 16, 512, 64
    attn = d * H * 192 + d * (r + dr) + r * H * 256 + H * 128 * d
    expert, shared, router = 3 * d * 1408, 3 * d * 2816, d * 64
    norms = 2 * d + r
    moe = attn + norms + 16 * expert + shared + router
    dense = attn + norms + 3 * d * 10944
    whole = 26 * moe + dense + 2 * 102400 * d + d
    model = Model(get_config(ARCH))
    assert model.count_params() == whole == 4_910_345_728
    assert model.active_params() == whole - 26 * (16 * expert
                                                  - 6 * 16 * expert // 64)


def test_yarn_frequencies_and_scales_at_published_settings():
    """Correction range 10..23 of the 32 rotary frequencies at beta 32/1 and
    4,096 original positions; mscale(40, 0.707) = 1.2608."""
    cfg = get_config(ARCH)
    inv, cs = yarn_rope(cfg)
    extra = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(inv[11:23], extra[11:23] * (1 - ramp)
                               + extra[11:23] / 40 * ramp, rtol=1e-6)
    assert cs == 1.0                       # mscale == mscale_all_dim
    m = 0.1 * 0.707 * math.log(40) + 1
    assert cfg.yarn_attn_scale == pytest.approx(m * m) \
        == pytest.approx(1.5896, abs=1e-4)
    assert get_config("minicpm3-4b").yarn_attn_scale == 1.0


@pytest.mark.parametrize("norm_topk", [False, True])
def test_dropless_layer_is_the_gate_weighted_sum(norm_topk):
    cfg = _f32(get_smoke_config(ARCH)).with_(moe_norm_topk=norm_topk)
    p = _moe_params(cfg, 1)
    x = jax.random.normal(jax.random.key(2), (3, 20, cfg.d_model))
    out, stats = moe_dropless(x, p, cfg)
    np.testing.assert_allclose(out, _naive_experts(x, p, cfg),
                               atol=2e-5, rtol=0)
    # each row counts its assignments to held experts; the largest load is
    # the same in every row
    _, top_e = jax.lax.top_k(x @ p["router"], cfg.experts_per_token)
    held = np.asarray(top_e < cfg.held_experts).reshape(3, -1).sum(-1)
    np.testing.assert_array_equal(stats[:, 0], held)
    loads = np.bincount(np.asarray(top_e).reshape(-1), minlength=8)[:4]
    assert (np.asarray(stats[:, 1]) == loads.max()).all()


def test_every_token_on_one_expert_drops_nothing():
    """A router that sends every token to the same experts (all gate logits
    equal: ties go to the lowest ids) over a 512-token prompt: the training
    layer's capacity of 240 drops most of it; the served layer drops
    nothing, at every length."""
    cfg = _f32(get_smoke_config(ARCH))
    p = _moe_params(cfg, 3, scale=0.0)
    x = jax.random.normal(jax.random.key(4), (1, 512, cfg.d_model))
    want = _naive_experts(x, p, cfg)
    got, stats = moe_dropless(x, p, cfg)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert int(stats[0, 0]) == 512 * cfg.experts_per_token
    assert int(stats[0, 1]) == 512
    dropped, _ = moe_ffn(x, p, cfg)
    assert float(jnp.abs(dropped - want).max()) > 0.1


def test_layer_indexes_whole_stacks_as_one_layer():
    """Served scans hand the expert layer every layer's experts whole and
    the layer's index: the same answer as that layer's slice."""
    cfg = _f32(get_smoke_config(ARCH))
    layers = [_moe_params(cfg, 10 + i) for i in range(3)]
    x = jax.random.normal(jax.random.key(5), (2, 7, cfg.d_model))
    stack = {k: jnp.stack([lp[k] for lp in layers])
             for k in ("w_gate", "w_up", "w_down")}
    for i, lp in enumerate(layers):
        whole = dict(lp, **stack, layer=jnp.int32(i))
        a, sa = moe_dropless(x, lp, cfg)
        b, sb = moe_dropless(x, whole, cfg)
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)
        np.testing.assert_array_equal(sa, sb)


def test_four_shares_with_shared_counted_once_sum_to_the_whole_layer():
    """Four chips of 2 experts each: their outputs, less the shared experts
    that every chip adds, sum to the layer that holds all 8."""
    whole = _f32(get_smoke_config(ARCH)).with_(n_held_experts=8)
    p = _moe_params(whole, 6)
    x = jax.random.normal(jax.random.key(7), (2, 9, whole.d_model))
    want, _, _ = moe_mlp(x, p, whole, dropless=True)
    total = 0.0
    for first in (0, 2, 4, 6):
        cfg = whole.with_(n_held_experts=2, first_held_expert=first)
        share = dict(p, **{k: p[k][first:first + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        out, _, _ = moe_mlp(x, share, cfg, dropless=True)
        total = total + out
    shared = swiglu_mlp(x, p["shared"])
    np.testing.assert_allclose(total - 3 * shared, want, atol=1e-5, rtol=0)


def test_layer_groups_and_the_cache_of_all_27_layers():
    model = Model(get_config(ARCH))
    params = model.abstract_params()
    assert jax.tree.leaves(params["dense_blocks"])[0].shape[0] == 1
    assert params["dense_blocks"]["mlp"]["w_gate"].shape == (1, 2048, 10944)
    assert params["blocks"]["mlp"]["w_gate"].shape == (26, 16, 2048, 1408)
    assert params["blocks"]["mlp"]["router"].shape == (26, 2048, 64)
    assert params["blocks"]["mlp"]["router"].dtype == jnp.float32
    assert params["blocks"]["attn"]["wq"].shape == (26, 2048, 16 * 192)
    assert "wq_a" not in params["blocks"]["attn"]
    cache = model.init_cache(64, 1024, abstract=True)
    assert cache["latent"].shape[0] + cache["dense_latent"].shape[0] == 27
    per_token = sum(math.prod(a.shape) * 2 for k, a in cache.items()
                    if k != "moe_stats") / (64 * 1024)
    assert per_token == (512 + 64) * 27 * 2                 # 31.1 KB
    assert cache["moe_stats"].shape == (1, 64, 2)


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config(ARCH)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # larger weights so that each request gets tokens of its own
    params = {**params, **{g: jax.tree.map(
        lambda w: w * 6 if w.ndim >= 3 and w.dtype != jnp.float32 else w,
        params[g]) for g in ("dense_blocks", "blocks")}}
    return model, params


def test_served_tokens_are_the_models_own_greedy_decode(smoke):
    """Through ``build_llm_app`` on fiber: each answer equals the engine's
    own prefill and decode run for that request alone."""
    model, params = smoke
    scfg = ServeConfig(max_batch=4, max_len=64, prefill_bucket=16,
                       max_new_tokens=6)
    app = build_llm_app(model, params, scfg, backend="fiber")
    eng = app.state["engine"]
    app.start()
    try:
        app.send("engine", "run", None)
        texts = ["mixture of experts", "latent", "yarn scaled rope", "dense"]
        futs = [app.send("api", "generate", {"text": t, "max_new": 6})
                for t in texts]
        outs = [f.wait(timeout=120)["tokens"] for f in futs]
    finally:
        app.services["engine"].state["stop"] = True
        app.stop()
    from repro.serving.service import tokenize
    want = [chip_smoke.greedy_reference(
        eng, tokenize(t, model.cfg.vocab_size), 6)[0] for t in texts]
    assert [list(map(int, o)) for o in outs] == want
    assert len({tuple(o) for o in outs}) > 1


def test_expert_counts_ride_on_the_step_spans(smoke):
    """``moe_local`` and ``moe_max_load`` on every prefill and decode step,
    from the one copy of the picked ids."""
    model, params = smoke
    eng = InferenceEngine(model, params, ServeConfig(
        max_batch=2, max_len=64, prefill_bucket=16, max_new_tokens=3))
    rec = SpanRecorder()
    rec.install()
    try:
        done = [eng.submit(np.arange(5, dtype=np.int32) + i) for i in (0, 9)]
        while not all(d.done for d in done):
            adm = eng.admit_one()
            if adm is not None:
                eng.do_prefill(adm[0])
            eng.do_decode_step()
    finally:
        rec.uninstall()
    cfg = model.cfg
    n_moe = cfg.n_layers - cfg.n_dense_layers
    steps = [s.attrs for s in rec.spans if s.name == "engine.decode_step"
             and s.attrs["active"] > 0]
    prefills = [s.attrs for s in rec.spans if s.name == "engine.prefill"]
    assert steps and len(prefills) == 2
    for a, rows in [(a, 2) for a in steps] + [(a, 16) for a in prefills]:
        # rows x top-k x layers assignments in all, a share of them held
        assert 0 < a["moe_local"] <= rows * cfg.experts_per_token * n_moe
        assert a["moe_local"] / n_moe >= a["moe_max_load"] / n_moe >= 1
    assert eng._pick(jnp.zeros((2, cfg.vocab_size)),
                     eng.cache["moe_stats"]).shape == (4,)


def test_half_expert_weights_serve_the_same_layer():
    """Experts stored as ``moe_expert_split`` sub-experts (each d_ff split
    in two, as the training layer shards them) are joined back for the
    grouped products: the same answer as the unsplit weights."""
    cfg = _f32(get_smoke_config(ARCH))
    p = _moe_params(cfg, 9)
    El, d, f, k = cfg.held_experts, cfg.d_model, cfg.d_ff, 2
    split = dict(p, w_gate=p["w_gate"].reshape(El, d, k, f // k)
                 .transpose(0, 2, 1, 3).reshape(El * k, d, f // k),
                 w_up=p["w_up"].reshape(El, d, k, f // k)
                 .transpose(0, 2, 1, 3).reshape(El * k, d, f // k),
                 w_down=p["w_down"].reshape(El * k, f // k, d))
    x = jax.random.normal(jax.random.key(11), (2, 5, d))
    a, sa = moe_dropless(x, p, cfg)
    b, sb = moe_dropless(x, split, cfg.with_(moe_expert_split=k))
    np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)
    np.testing.assert_array_equal(sa, sb)
