"""DeepSeek-V2-Lite in the benchmark: the plain float32 reference against the
program (prefill, then decode through the cache), the share of the expert
layer held here against the uncut reference layer, a skewed router, the
configuration file, the counts and the expert layer's readers; on the CPU
at smoke size."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import flops, harness, reference, weights
from perfbench.devtrace import Event, Trace
from perfbench.harness import HERE, ROOT, reader
from perfbench.references import deepseek_v2 as ds
from perfbench.tests import smoke
from perfbench.tests.test_perfbench_reference import (SEED, model_of,
                                                      served_logits)

# 1 dense layer and 3 expert layers; 8 routed experts of which 4 are held,
# 2 shared, no q_lora, YaRN whose original length (8) lies below the
# sequences compared here
SMOKE = {
    "reference": "deepseek_v2", "torch_dtype": "bfloat16",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": None,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 4, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 8},
    "deployment": {"n_routed_experts": 8, "first_held_expert": 0},
    "program": {
        "name": "deepseek-v2-smoke", "family": "moe", "n_layers": 4,
        "d_model": 64, "d_ff": 32, "vocab_size": 512, "n_heads": 4,
        "n_kv_heads": 4, "head_dim": 16, "use_mla": True, "q_lora_rank": 0,
        "kv_lora_rank": 16, "qk_nope_dim": 16, "qk_rope_dim": 8,
        "v_head_dim": 16, "norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_embeddings": False, "yarn_factor": 4.0, "yarn_original_len": 8,
        "yarn_mscale": 0.707, "yarn_mscale_all_dim": 0.707, "n_experts": 8,
        "experts_per_token": 3, "moe_norm_topk": False,
        "n_shared_experts": 2, "n_held_experts": 4, "first_held_expert": 0,
        "n_dense_layers": 1, "dense_d_ff": 96, "remat": False}}


def variant(dtype="bfloat16", norm_topk=False, held=4, first=0, **scaling):
    c = json.loads(json.dumps(SMOKE))
    c["torch_dtype"] = dtype
    c["norm_topk_prob"] = c["program"]["moe_norm_topk"] = norm_topk
    c["n_routed_experts"] = c["program"]["n_held_experts"] = held
    c["deployment"]["first_held_expert"] = first
    c["program"]["first_held_expert"] = first
    c["rope_scaling"].update(scaling)
    c["program"]["yarn_mscale"] = c["rope_scaling"]["mscale"]
    return c


def served_params(c, dtype):
    model = model_of(c, dtype)
    return model, weights.make_params(
        jax.eval_shape(model.init, jax.random.key(0)), model.cfg.n_layers,
        SEED)


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("mscale", [0.707, 1.0])
def test_reference_matches_model_in_float32(norm_topk, mscale):
    """Prefill of 16 then 8 decode steps through the cache against the
    reference's full forward.  In float32 on the CPU the two differ in
    summation order only: logits of magnitude about 0.5 agree to 1e-5
    (measured 1.5e-7).  ``mscale`` 1.0 beside ``mscale_all_dim`` 0.707 puts
    a factor of 1.044 on cos and sin."""
    c = variant("float32", norm_topk, mscale=mscale)
    model, params = served_params(c, "float32")
    P, n = 16, 8
    toks = np.random.default_rng(1).integers(0, 512, P + n).astype(np.int32)
    prog, _ = served_logits(model, params, toks, P)
    ref = reference.forward(c, SEED, [toks[:-1]],
                            [np.arange(P - 1, P + n - 1)])[None][0]
    np.testing.assert_allclose(prog, np.asarray(ref), atol=1e-5, rtol=0)


def test_gaps_of_bfloat16_served_tokens():
    """The served bfloat16 tokens have gap 0 or a rounding-sized one (under
    0.05, as for the other kinds); tokens shifted by one have gaps of the
    logits' spread (0.1 or more here)."""
    c = variant()
    model, params = served_params(c, "bfloat16")
    P, n = 16, 8
    prompt = np.random.default_rng(2).integers(0, 512, P).astype(np.int32)
    logits, fed = served_logits(model, params, prompt, P, greedy=n - 1)
    served = np.argmax(logits, -1).astype(np.int32)
    ref, ctrl = reference.gaps(c, SEED, [fed], [served], P - 1, P + n,
                               ("fp8",))
    assert ref[0].shape == (n,) and ref[0].max() < 0.05
    assert ctrl["fp8"][0].shape == (n,)
    wrong, _ = reference.gaps(c, SEED, [fed], [(served + 1) % 512], P - 1,
                              P + n)
    assert wrong[0].max() > 0.1


def test_a_router_that_sends_every_token_to_one_place_drops_nothing(
        monkeypatch):
    """With every gate logit 0 each token picks experts 0, 1, 2 (ties go to
    the lowest ids), so the held ones get every token of a 40-token prompt:
    prefill and decode still give the reference's answer (float32, 1e-5 as
    above)."""
    c = variant("float32")
    model, params = served_params(c, "float32")
    params["blocks"]["mlp"]["router"] = jnp.zeros_like(
        params["blocks"]["mlp"]["router"])
    draw = ds.layer_weights

    def zero_router(seed, cfg, layer):
        w = draw(seed, cfg, layer)
        return {p: jnp.zeros_like(a) if p[-1] == "router" else a
                for p, a in w.items()}

    monkeypatch.setattr(ds, "layer_weights", zero_router)
    P, n = 40, 4
    toks = np.random.default_rng(3).integers(0, 512, P + n).astype(np.int32)
    prog, _ = served_logits(model, params, toks, P)
    ref = reference.forward(c, SEED, [toks[:-1]],
                            [np.arange(P - 1, P + n - 1)])[None][0]
    np.testing.assert_allclose(prog, np.asarray(ref), atol=1e-5, rtol=0)
    # the capacity the training layer would give this prompt: 18 < 40
    assert int(P * 3 / 8 * model.cfg.moe_capacity_factor) < P


def test_four_held_shares_sum_to_the_uncut_reference_layer():
    """The program's expert layer on each of four chips' shares (2 of the 8
    experts each), with the shared experts that every chip adds counted
    once, sums to the reference's layer that holds all 8 (float32)."""
    from repro.models.layers import moe_mlp, swiglu_mlp
    whole = variant("float32", held=8)
    w = ds.layer_weights(SEED, whole, 2)
    m = {p[-1] if len(p) == 3 else p[-2] + "/" + p[-1]: a
         for p, a in w.items() if p[1] == "mlp"}
    mlp = {"router": m["router"], "w_gate": m["w_gate"], "w_up": m["w_up"],
           "w_down": m["w_down"],
           "shared": {k: m["shared/" + k] for k in ("w_gate", "w_up",
                                                    "w_down")}}
    h = jax.random.normal(jax.random.key(8), (1, 12, 64), jnp.float32)
    want = ds.experts(h[0], mlp, whole, None) \
        + np.asarray(swiglu_mlp(h, mlp["shared"]))[0]
    total = 0.0
    for first in (0, 2, 4, 6):
        cfg = model_of(variant("float32", held=2, first=first),
                       "float32").cfg
        share = dict(mlp, **{k: mlp[k][first:first + 2]
                             for k in ("w_gate", "w_up", "w_down")})
        out, _, _ = moe_mlp(h, share, cfg, dropless=True)
        total = total + out[0]
    total = total - 3 * swiglu_mlp(h, mlp["shared"])[0]
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=0)
    # and the reference's own shares sum to it too
    parts = sum(ds.experts(h[0], dict(mlp, **{
        k: mlp[k][f:f + 2] for k in ("w_gate", "w_up", "w_down")}),
        variant("float32", held=2, first=f), None) for f in (0, 2, 4, 6))
    np.testing.assert_allclose(parts, ds.experts(h[0], mlp, whole, None),
                               atol=1e-6, rtol=0)


def test_layer_weights_are_the_served_ones_in_both_groups():
    c = variant()
    _, params = served_params(c, "bfloat16")
    for layer, (group, i) in enumerate([("dense_blocks", 0), ("blocks", 0),
                                        ("blocks", 1), ("blocks", 2)]):
        for path, leaf in ds.layer_weights(SEED, c, layer).items():
            assert path[0] == group
            served = params
            for k in path:
                served = served[k]
            np.testing.assert_array_equal(np.asarray(served[i], np.float32),
                                          np.asarray(leaf), err_msg=path)
    assert params["blocks"]["mlp"]["router"].dtype == jnp.float32


def published():
    return json.loads((HERE / "configs" / "deepseek-v2-lite.json").read_text())


def test_config_file_states_the_published_sizes_and_the_cut():
    from repro.configs import get_config
    c = published()
    catalog = {     # the published config.json's numbers and switches
        "first_k_dense_replace": 1, "hidden_size": 2048,
        "intermediate_size": 10944, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "moe_intermediate_size": 1408,
        "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2,
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts_per_tok": 6, "num_hidden_layers": 27,
        "num_key_value_heads": 16, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "routed_scaling_factor": 1,
        "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 102400,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}}
    for k, v in catalog.items():
        assert c[k] == v, k
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(e for e in bench["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == list(c["reduced"]) == ["n_routed_experts"]
    assert (c["n_routed_experts"], c["deployment"]["n_routed_experts"],
            c["deployment"]["first_held_expert"],
            c["deployment"]["chips_per_layer"]) == (16, 64, 0, 4)
    p, prog = c["program"], get_config(c["name"])
    for key, value in p.items():
        if key not in ("remat", "param_dtype", "compute_dtype"):
            assert getattr(prog, key) == value, key
    rs = c["rope_scaling"]
    assert (p["n_layers"], p["d_model"], p["d_ff"], p["dense_d_ff"],
            p["n_dense_layers"], p["vocab_size"], p["n_heads"],
            p["kv_lora_rank"], p["q_lora_rank"], p["n_experts"],
            p["experts_per_token"], p["n_shared_experts"],
            p["n_held_experts"], p["moe_norm_topk"], p["yarn_factor"],
            p["yarn_original_len"], p["yarn_mscale"],
            p["yarn_mscale_all_dim"]) == (
        c["num_hidden_layers"], c["hidden_size"], c["moe_intermediate_size"],
        c["intermediate_size"], c["first_k_dense_replace"], c["vocab_size"],
        c["num_attention_heads"], c["kv_lora_rank"], 0,
        c["deployment"]["n_routed_experts"], c["num_experts_per_tok"],
        c["n_shared_experts"], c["n_routed_experts"], c["norm_topk_prob"],
        rs["factor"], rs["original_max_position_embeddings"], rs["mscale"],
        rs["mscale_all_dim"])
    assert set(c["assumed"]) >= {"rope_layout", "weights", "tokenizer"}


def test_counts_by_hand():
    c = flops.counts(published())
    assert type(c) is ds.ExpertCounts
    d = 2048
    attn = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    assert attn == 13_762_560
    expert = 3 * d * 1408                           # 8,650,752
    # 27 attentions, the dense MLP, 26 x (shared + router), the head, plus
    # 1.5 expected held experts per expert layer
    dense = 27 * attn + 3 * d * 10944 + 26 * (3 * d * 2816 + d * 64) \
        + 102400 * d
    assert c.matmul_weights == dense + 26 * 12_976_128
    assert c.attn_flops_per_ctx == 27 * (2 * 16 * 192 + 2 * 16 * 128)
    norms = 27 * (2 * d + 512) + d
    assert c.weight_bytes == 2 * (dense - 26 * d * 64 + norms) \
        + 4 * 26 * d * 64
    assert c.kv_bytes_per_token == 27 * 576 * 2 == 31_104
    # at 64 active tokens nearly every held expert is touched: 7.2 GB
    assert c.expert_bytes(64) == pytest.approx(
        26 * 16 * (1 - (58 / 64) ** 64) * expert * 2)
    assert 7.18e9 < c.expert_bytes(64) < 7.2e9
    assert c.expert_bytes(1) == pytest.approx(26 * 16 * 6 / 64 * expert * 2)
    assert c.expert_flops(64) == pytest.approx(2 * 64 * 26 * 1.5 * expert)
    assert c.decode_bytes(64, 1000) == pytest.approx(
        c.weight_bytes + 31_104 * 1000 + 64 * (d * 2 + 102400 * 4)
        + c.expert_bytes(64))


def trace_with_experts():
    """Two decode runs inside the window (10-40, 50-80 ns), one past it;
    grouped products of 5+3 and 4+4 ns in them, one outside every run."""
    mods = [Event("jit_decode_step(7)", 10, 40),
            Event("jit_decode_step(7)", 50, 80),
            Event("jit_decode_step(7)", 95, 130)]
    ops = [Event("jit_decode_step/ragged-dot-none.1", 12, 17),
           Event("jit_decode_step/ragged-dot-metadata", 18, 21),
           Event("jit_decode_step/fusion.3", 21, 39),
           Event("jit_decode_step/ragged-dot-none.2", 52, 56),
           Event("jit_decode_step/ragged-dot-none", 60, 64),
           Event("jit_prefill/ragged-dot-none", 85, 90),
           Event("jit_decode_step/ragged-dot-none", 100, 110)]
    return Trace(window=(0, 100), ops={"/device:TPU:0": ops},
                 modules={"/device:TPU:0": mods})


def test_expert_readers_on_a_trace_built_by_hand():
    c = flops.counts(published())
    run = harness.Run(
        cell=None, seconds=1.0, t0=0.0, t1=1.0, setup_s=1.0, loop="closed",
        requests=[], schedule=[], tokens_in_window=0, counts=c,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        decodes=[{"start": 0.5, "end": 0.6, "active": 64, "ctx": 9000}],
        trace=trace_with_experts(), trace_host=(0.0, 1.0))
    ms = reader(HERE, "moe.expert_ms")(run)
    assert ms == pytest.approx((5 + 3 + 4 + 4) / 2 / 1e6)
    least = max(c.expert_flops(64) / 197e12, c.expert_bytes(64) / 819e9)
    assert reader(HERE, "moe.expert_roofline")(run) == pytest.approx(
        100 * least / (ms / 1e3))
    # a run of another configuration, or with no trace: nothing to read
    run.counts = flops.counts(json.loads(
        (HERE / "configs" / "minicpm3-4b.json").read_text()))
    assert reader(HERE, "moe.expert_roofline")(run) is None
    run.trace = None
    assert reader(HERE, "moe.expert_ms")(run) is None
    assert reader(HERE, "moe.expert_roofline")(run) is None


def test_a_smoke_expert_cell_runs_through_the_harness(tree):
    """The served path end to end on the CPU: the engine's prefill and
    decode of the dropless expert layer, checked against the reference."""
    root, base = tree
    (base / "configs" / "deepseek-v2-smoke.json").write_text(
        json.dumps(variant()))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    smoke.write_cell(base, bench, "deepseek-v2-smoke-batch",
                     "deepseek-v2-smoke", "batch-smoke")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = harness.execute("deepseek-v2-smoke-batch", 2**31 + 77, 1.0, True,
                          time.perf_counter(), allow_cpu=True, root=root,
                          base=base)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["sampled_tokens"]["value"] >= 1
    assert 0 < res["metrics"]["engine.batch_occupancy"]["value"] <= 100
