"""Compile for a described TPU v5e chip (no chip attached): every Pallas
kernel at the widths of the config that would use it, and qwen2-0.5b's
served steps at chip_smoke.py's ServeConfig.

The TPU compiler refuses here what the chip would refuse (block shapes off
the (8, 128) tiling, unaligned dynamic slices, too much VMEM or HBM).  The
topology is described inside a fixture, never at import, so that under
several pytest workers only the worker given this file loads the TPU
library.  Nothing runs; these tests say nothing about results or speed.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention_fwd
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.rglru_scan.kernel import rglru_scan_fwd
from repro.kernels.rwkv6_scan.kernel import wkv6_fwd
from repro.models import Model
from repro.serving import InferenceEngine
from test_chip_smoke import chip_smoke

HBM_BYTES = 16 * 2**30          # one v5e chip
SERVE = chip_smoke.SERVE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip cannot be read back from the
        # persistent cache without one; keep them out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args, one_chip):
    placed = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args)
    return jax.jit(fn).lower(*placed).compile()


sd = jax.ShapeDtypeStruct
bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
_QWEN_Q, _QWEN_KV = (14, 64), (2, 64)          # heads, head_dim
_B, _T, _P = SERVE.max_batch, SERVE.max_len, SERVE.prefill_bucket

KERNELS = {
    # qwen2-0.5b decode at the ServeConfig: (B, Hq, D) over (B, T, Hkv, D)
    "decode_attention-qwen2-0.5b": (
        decode_attention_fwd,
        (sd((_B,) + _QWEN_Q, bf16), sd((_B, _T) + _QWEN_KV, bf16),
         sd((_B, _T) + _QWEN_KV, bf16), sd((_B,), i32))),
    # qwen2-0.5b prefill of one bucket, and of a whole max_len prompt
    "flash_attention-qwen2-0.5b-bucket": (
        flash_attention_fwd,
        (sd((1, _P) + _QWEN_Q, bf16), sd((1, _P) + _QWEN_KV, bf16),
         sd((1, _P) + _QWEN_KV, bf16))),
    "flash_attention-qwen2-0.5b-max_len": (
        flash_attention_fwd,
        (sd((1, _T) + _QWEN_Q, bf16), sd((1, _T) + _QWEN_KV, bf16),
         sd((1, _T) + _QWEN_KV, bf16))),
    # rwkv6-3b: 40 heads of 64
    "rwkv6_scan-rwkv6-3b": (
        wkv6_fwd,
        (sd((1, _P, 40, 64), bf16), sd((1, _P, 40, 64), bf16),
         sd((1, _P, 40, 64), bf16), sd((1, _P, 40, 64), f32),
         sd((40, 64), bf16), sd((1, 40, 64, 64), f32))),
    # recurrentgemma-9b: lru_width 4096, two sequences
    "rglru_scan-recurrentgemma-9b": (
        rglru_scan_fwd,
        (sd((2, _P, 4096), bf16), sd((2, _P, 4096), bf16),
         sd((2, 4096), f32))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = KERNELS[name]
    compiled = _compile(fn, args, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen(one_chip):
    model = Model(get_config("qwen2-0.5b").with_(remat=False))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = model.init_cache(_B, _T, abstract=True)
    return model, params, cache


def _qwen_step(kind, model, params, cache):
    tokens = {"tokens": sd((1, _P), i32)}
    if kind == "prefill":
        return model.prefill, (params, tokens)
    if kind == "decode_step":
        return model.decode_step, (params, cache, sd((_B, 1), i32),
                                   sd((_B,), i32))
    pcache = jax.eval_shape(model.prefill, params, tokens)[1]
    return InferenceEngine._insert_impl, (cache, pcache, sd((), i32))


@pytest.mark.parametrize("kind", ("prefill", "decode_step", "insert"))
def test_qwen2_served_step_compiles_for_v5e(kind, qwen, one_chip):
    fn, args = _qwen_step(kind, *qwen)
    mem = _compile(fn, args, one_chip).memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES
