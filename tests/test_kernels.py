"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode on CPU; the kernels target TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.rwkv6_scan.ops import wkv6
from repro.kernels.rwkv6_scan.ref import wkv6_ref


def _assert_close(a, b, dtype, atol32=3e-5, atolbf=3e-2):
    atol = atolbf if dtype == jnp.bfloat16 else atol32
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=atol, rtol=atol)


# --------------------------------------------------------- flash attention
FA_CASES = [
    # B, S, T, Hq, Hkv, D, causal, window, softcap
    (2, 256, 256, 4, 2, 64, True, 0, 0.0),
    (1, 512, 512, 8, 8, 128, True, 0, 0.0),
    (1, 256, 512, 4, 1, 64, True, 0, 30.0),
    (2, 256, 256, 4, 2, 128, True, 128, 0.0),
    (1, 256, 256, 2, 2, 64, False, 0, 0.0),
    (1, 1024, 1024, 2, 1, 64, True, 256, 0.0),
]


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, S, T, Hq, Hkv, D, causal, window, cap = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal, window, cap)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=cap)
    assert out.dtype == dtype
    _assert_close(out, ref, dtype)


def test_flash_attention_grad_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    g1 = jax.grad(lambda q, k, v: flash_attention(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: flash_attention_ref(q, k, v).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        _assert_close(a, b, jnp.float32)


# ----------------------------------------------------------- flash decode
FD_CASES = [
    (2, 1024, 8, 2, 64, 0.0),
    (4, 512, 4, 1, 128, 0.0),
    (2, 2048, 16, 8, 128, 30.0),
    (1, 512, 14, 2, 64, 0.0),     # qwen2-0.5b head geometry
]


@pytest.mark.parametrize("case", FD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(case, dtype):
    B, T, Hq, Hkv, D, cap = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    out = decode_attention(q, k, v, lengths, softcap=cap)
    ref = decode_attention_ref(q, k, v, lengths, softcap=cap)
    _assert_close(out, ref, dtype)


def _check_decode_attention_case(B, T, heads, D):
    """Property body: kernel == oracle for arbitrary (B,T,heads,D,lengths)."""
    Hq, Hkv = heads
    ks = jax.random.split(jax.random.PRNGKey(B * T + Hq + D), 4)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    _assert_close(decode_attention(q, k, v, lengths),
                  decode_attention_ref(q, k, v, lengths), jnp.float32)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4), st.sampled_from([256, 512]),
       st.sampled_from([(4, 2), (8, 1), (2, 2)]),
       st.sampled_from([64, 128]))
def test_decode_attention_property(B, T, heads, D):
    _check_decode_attention_case(B, T, heads, D)


# ------------------------------------------------------------------ wkv6
WKV_CASES = [
    (2, 128, 2, 64),
    (1, 256, 4, 64),
    (2, 64, 1, 32),
    (1, 512, 2, 64),
]


@pytest.mark.parametrize("case", WKV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_sweep(case, dtype):
    B, T, H, D = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 6)
    r = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, H, D), dtype)
    v = jax.random.normal(ks[2], (B, T, H, D), dtype)
    x = jax.random.uniform(ks[3], (B, T, H, D), minval=-6.0, maxval=1.0)
    w = jnp.exp(-jnp.exp(x)).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (H, D)) * 0.3).astype(dtype)
    s0 = (jax.random.normal(ks[5], (B, H, D, D)) * 0.1).astype(jnp.float32)
    out, sT = wkv6(r, k, v, w, u, s0)
    oref, sref = wkv6_ref(r, k, v, w, u, s0)
    _assert_close(out, oref, dtype, atol32=3e-4, atolbf=5e-2)
    _assert_close(sT, sref, jnp.float32, atol32=3e-4)


def test_wkv6_extreme_decay_stable():
    """Strong decays underflow to 0 harmlessly (no NaN/Inf)."""
    B, T, H, D = 1, 128, 1, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    r = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    w = jnp.full((B, T, H, D), 1e-4)        # near-total forgetting
    u = jnp.zeros((H, D))
    s0 = jnp.zeros((B, H, D, D))
    out, sT = wkv6(r, k, v, w, u, s0)
    oref, _ = wkv6_ref(r, k, v, w, u, s0)
    assert np.all(np.isfinite(np.asarray(out)))
    _assert_close(out, oref, jnp.float32, atol32=1e-3)


# ------------------------------------------------------------- rglru scan
@pytest.mark.parametrize("shape", [(2, 256, 512), (1, 128, 1024),
                                   (3, 64, 128), (1, 512, 256),
                                   (2, 40, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(shape, dtype):
    B, T, W = shape
    ks = jax.random.split(jax.random.PRNGKey(B + T + W), 3)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, W))) ** 0.2).astype(dtype)
    b = (jax.random.normal(ks[1], (B, T, W)) * 0.3).astype(dtype)
    h0 = jax.random.normal(ks[2], (B, W), jnp.float32)
    h, hT = rglru_scan(a, b, h0)
    href, hTref = rglru_scan_ref(a, b, h0)
    _assert_close(h, href, dtype)
    _assert_close(hT, hTref, jnp.float32, atol32=1e-4, atolbf=5e-2)


def test_rglru_scan_grad_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 64, 128))) ** 0.2
    b = jax.random.normal(ks[1], (1, 64, 128)) * 0.3
    h0 = jax.random.normal(ks[2], (1, 128))
    g1 = jax.grad(lambda a, b: rglru_scan(a, b, h0)[0].sum(),
                  argnums=(0, 1))(a, b)
    g2 = jax.grad(lambda a, b: rglru_scan_ref(a, b, h0)[0].sum(),
                  argnums=(0, 1))(a, b)
    for x, y in zip(g1, g2):
        _assert_close(x, y, jnp.float32)


# --------------------------------------------- model-level kernel parity
def test_rwkv_model_kernel_path_matches_ref_path():
    """The full rwkv6 smoke model gives the same loss with the Pallas
    chunked kernel as with the lax.scan reference."""
    from repro.configs import get_smoke_config
    from repro.models import Model
    cfg = get_smoke_config("rwkv6-3b").with_(remat=False)
    rng = jax.random.PRNGKey(0)
    tok = jax.random.randint(rng, (2, 64), 0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    m_ref = Model(cfg, use_kernels=False)
    m_ker = Model(cfg, use_kernels=True)
    params = m_ref.init(rng)
    l1, _ = m_ref.loss(params, batch)
    l2, _ = m_ker.loss(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-2)
