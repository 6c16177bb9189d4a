"""Decode/prefill parity: for every architecture, decoding token S after a
prefill of S tokens must reproduce the logits of a full (S+1)-token prefill.
This exercises KV caches (full/MLA/window/ring), recurrent states, and
position handling end-to-end, in fp32 for tight tolerances."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import Model

B, S = 2, 12  # decode the (S+1)-th token


def _fp32(cfg):
    # fp32 for tight tolerances; huge MoE capacity so no tokens are dropped
    # (capacity dropping legitimately differs between prefill lengths)
    return cfg.with_(param_dtype="float32", compute_dtype="float32",
                     remat=False, moe_capacity_factor=16.0)


def _inputs(model, rng, seq):
    cfg = model.cfg
    tok = jax.random.randint(rng, (B, seq), 0, cfg.vocab_size)
    inp = {"tokens": tok}
    if cfg.family == "vlm":
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (B, seq))
        inp["positions"] = jnp.stack([pos, pos, pos])
    if cfg.is_encdec:
        src = jax.random.normal(jax.random.fold_in(rng, 1),
                                (B, 16, cfg.d_model), jnp.float32) * 0.02
        inp = {"src": src, "tokens": tok}
    return inp


def _pad_seq_caches(cfg, cache, max_len):
    if cfg.family in ("ssm", "hybrid"):
        return cache

    def pad(x, axis=2):
        n = max_len - x.shape[axis]
        if n <= 0:
            return x
        w = [(0, 0)] * x.ndim
        w[axis] = (0, n)
        return jnp.pad(x, w)

    if cfg.is_encdec:
        return {"self": {k: pad(v) for k, v in cache["self"].items()},
                "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
    return {k: pad(v) for k, v in cache.items()}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_prefill(arch):
    cfg = _fp32(get_smoke_config(arch))
    model = Model(cfg)
    rng = jax.random.PRNGKey(42)
    params = model.init(rng)

    full = _inputs(model, rng, S + 1)
    prefix = dict(full)
    prefix["tokens"] = full["tokens"][:, :S]
    if "positions" in full:
        prefix["positions"] = full["positions"][:, :, :S]

    # reference: last-token logits from a full (S+1)-length prefill
    ref_logits, _ = jax.jit(model.prefill)(params, full)

    # candidate: prefill S tokens, then decode token S from the cache
    _, cache = jax.jit(model.prefill)(params, prefix)
    cache = _pad_seq_caches(cfg, cache, S + 4)
    tok = full["tokens"][:, S:S + 1]
    pos = jnp.full((B,), S, jnp.int32)
    dec_logits, _ = jax.jit(model.decode_step)(params, cache, tok, pos)

    a = np.asarray(ref_logits, np.float32)
    b = np.asarray(dec_logits, np.float32)
    np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3,
                               err_msg=f"{arch}: decode != prefill")


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "minicpm3-4b",
                                  "deepseek-v2-lite"])
def test_decode_steps_match_prefill(arch):
    """Three decode steps after a prefill, each writing its token's entries
    into the cache after its layers ran, reproduce the last-token logits of
    full prefills of S+1, S+2 and S+3 tokens, the two slots at different
    positions."""
    cfg = _fp32(get_smoke_config(arch))
    model = Model(cfg)
    rng = jax.random.PRNGKey(7)
    params = model.init(rng)
    full = _inputs(model, rng, S + 3)
    _, cache = jax.jit(model.prefill)(params, {"tokens": full["tokens"][:, :S]})
    cache = _pad_seq_caches(cfg, cache, S + 8)
    # slot 1 lags one token behind slot 0: its first step re-decodes the
    # prompt's last token over the entries of the S - 1 before it
    lag = np.array([0, 1])
    step = jax.jit(model.decode_step)
    for i in range(3):
        n = S + i - lag                                   # tokens before
        tok = full["tokens"][jnp.arange(B), n][:, None]
        logits, cache = step(params, cache, tok, jnp.asarray(n, jnp.int32))
        for b in range(B):
            ref, _ = jax.jit(model.prefill)(
                params, {"tokens": full["tokens"][b:b + 1, :n[b] + 1]})
            np.testing.assert_allclose(
                np.asarray(logits[b]), np.asarray(ref[0]), atol=2e-3,
                rtol=2e-3, err_msg=f"{arch}: step {i}, slot {b}")


@pytest.mark.parametrize("width", [128, 24])
def test_write_entries_writes_each_slot_row(width):
    """Both ways of writing a step's entries (one scatter of lane-wide rows,
    one update per slot of narrower ones) set row ``pos[b]`` of every
    layer and slot and leave every other entry as it was."""
    from repro.models.kv_cache import write_entries
    L, nb, T = 3, 4, 10
    rng = np.random.default_rng(width)
    leaf = rng.standard_normal((L, nb, T, width)).astype(np.float32)
    new = rng.standard_normal((L, nb, width)).astype(np.float32)
    pos = np.array([0, 9, 4, 4], np.int32)
    want = leaf.copy()
    want[:, np.arange(nb), pos] = new
    got = jax.jit(write_entries)(jnp.asarray(leaf), jnp.asarray(new),
                                 jnp.asarray(pos))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("arch", ["rwkv6-3b", "recurrentgemma-9b"])
def test_long_context_state_is_bounded(arch):
    """Sub-quadratic archs: decode-state byte size is independent of the
    context length (the long_500k feasibility property)."""
    cfg = get_smoke_config(arch)
    model = Model(cfg)

    def nbytes(max_len):
        cache = model.init_cache(1, max_len, abstract=True)
        return sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(cache))

    assert nbytes(1 << 10) == nbytes(1 << 19)
