"""Compile for a described TPU v5e chip (no chip attached): every Pallas
kernel at the widths of the config that would use it, and qwen2-0.5b's
served steps at chip_smoke.py's ServeConfig.

The TPU compiler refuses here what the chip would refuse (block shapes off
the (8, 128) tiling, unaligned dynamic slices, too much VMEM or HBM).  The
topology is described inside a fixture, never at import, so that under
several pytest workers only the worker given this file loads the TPU
library.  Nothing runs; these tests say nothing about results or speed.
"""
import re

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
from repro.serving import InferenceEngine, ServeConfig
from repro.serving.engine import _greedy_pick
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


def _place(args, one_chip):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args)


def _compile(fn, args, one_chip, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(
        *_place(args, one_chip)).compile()


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
    if kind == "pick":
        return _greedy_pick, (sd((_B, model.cfg.vocab_size), f32),)
    pcache = jax.eval_shape(model.prefill, params, tokens)[1]
    return InferenceEngine._insert_impl, (cache, pcache, sd((), i32))


@pytest.mark.parametrize("kind", ("prefill", "decode_step", "insert", "pick"))
def test_qwen2_served_step_compiles_for_v5e(kind, qwen, one_chip):
    fn, args = _qwen_step(kind, *qwen)
    mem = _compile(fn, args, one_chip).memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES


_DS = dict(max_batch=64, max_len=1024, prefill_bucket=512)   # the cell's


@pytest.fixture(scope="module")
def deepseek(one_chip):
    model = Model(get_config("deepseek-v2-lite").with_(remat=False))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = model.init_cache(_DS["max_batch"], _DS["max_len"], abstract=True)
    return model, params, cache


@pytest.mark.parametrize("kind", ("prefill", "decode_step"))
def test_deepseek_v2_lite_served_step_compiles_for_v5e(kind, deepseek,
                                                       one_chip):
    """The dropless expert layer's grouped products lower to the TPU's
    ragged-dot kernel, and the decode step, on a donated cache as the
    engine serves it, copies no stack of the expert layers' cache: each
    group's layers read their own leaves in place, and the experts' stacks
    are read in place.  (Without donation a step must copy the cache it
    returns.)"""
    model, params, cache = deepseek
    B = _DS["max_batch"]
    donate = ()
    if kind == "prefill":
        fn, args = model.prefill, (params,
                                   {"tokens": sd((1, _DS["prefill_bucket"]),
                                                 i32)})
    else:
        fn, args = model.decode_step, (params, cache, sd((B, 1), i32),
                                       sd((B,), i32))
        donate = (1,)
    compiled = _compile(fn, args, one_chip, donate)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    if kind == "decode_step":
        leaves = {"bf16[" + ",".join(map(str, a.shape)) + "]"
                  for k, a in cache.items() if a.shape[0] > 1}
        copies = [line for line in text.splitlines()
                  if " copy(" in line
                  and line.split(" = ", 1)[-1].split("{", 1)[0] in leaves]
        assert copies == []


# the cells' engines: (max_batch, max_len, prefill_bucket)
ENGINES = {"qwen2-0.5b": (64, 1024, 256), "minicpm3-4b": (32, 1536, 512),
           "deepseek-v2-lite": (64, 1024, 512)}


def _copied_dims(line):
    """The dimensions a ``copy`` or ``copy-start`` writes, or None for
    another instruction or for a copy-start that only moves an array
    between memory spaces in the layout it has (a prefetch, which a fusion
    then reads from the core's own memory)."""
    m = re.search(r"= (\(?)(.*?) copy(-start)?\(", line)
    if m is None:
        return None
    arrays = re.findall(r"\w+\[([\d,]*)\](\{[^}]*\})?", m.group(2))
    if m.group(3):
        (dims, dst), (_, src) = arrays[:2]
        if re.sub(r"S\(\d\)", "", dst) == re.sub(r"S\(\d\)", "", src):
            return None
    return tuple(int(d) for d in arrays[0][0].split(",") if d)


@pytest.mark.parametrize("program", ("decode", "insert"))
@pytest.mark.parametrize("arch", sorted(ENGINES))
def test_engine_moves_the_cache_in_place_on_v5e(arch, program, one_chip):
    """The engine's donated decode step and insert at a cell's size update
    the cache where it lies: the whole cache is aliased from input to
    output, the temporaries stay below one layer's slab of it, and no copy
    has the shape of a layer's slab or of a whole leaf, save a prefetch
    into the core's memory in the layout the leaf has."""
    B, T, P = ENGINES[arch]
    model = Model(get_config(arch).with_(remat=False))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    engine = InferenceEngine(model, params, ServeConfig(
        max_batch=1, max_len=8, prefill_bucket=4))       # programs only
    cache = model.init_cache(B, T, abstract=True)
    if program == "decode":
        fn, args = engine._step, (params, cache, sd((B, 1), i32),
                                  sd((B,), i32))
    else:
        pcache = jax.eval_shape(model.prefill, params,
                                {"tokens": sd((1, P), i32)})[1]
        fn, args = engine._insert, (cache, pcache, sd((), i32))
    compiled = fn.lower(*_place(args, one_chip)).compile()
    mem = compiled.memory_analysis()
    # the leaves of entries; the expert counts, a few hundred bytes that
    # each step writes anew, may be aliased too, in a device tile
    leaves = [a for k, a in cache.items() if k != "moe_stats"]
    nbytes = [a.size * a.dtype.itemsize for a in leaves]
    assert 0 <= mem.alias_size_in_bytes - sum(nbytes) < 4096
    slab = max(n // a.shape[0] for a, n in zip(leaves, nbytes))
    assert mem.temp_size_in_bytes < slab
    shapes = set()
    for a in leaves:
        shapes |= {a.shape, a.shape[1:], (1,) + a.shape[1:]}
    copies = [line for line in compiled.as_text().splitlines()
              if _copied_dims(line) in shapes]
    assert copies == []
