"""Serving engine integration tests: continuous batching over the fiber
(and baseline thread) runtimes with a tiny model."""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model
from repro.serving import ServeConfig, build_llm_app
from repro.serving.engine import InferenceEngine

BACKENDS = ("fiber", "thread")


def _tiny_model(arch="qwen2-0.5b"):
    cfg = get_smoke_config(arch).with_(remat=False)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _stop(app):
    app.services["engine"].state["stop"] = True
    time.sleep(0.05)
    app.stop()


def test_engine_direct_generation():
    model, params = _tiny_model()
    scfg = ServeConfig(max_batch=2, max_len=96, prefill_bucket=16,
                       max_new_tokens=4)
    eng = InferenceEngine(model, params, scfg)
    done = eng.submit(np.arange(8, dtype=np.int32) % model.cfg.vocab_size)
    adm = eng.admit_one()
    assert adm is not None
    eng.do_prefill(adm[0])
    for _ in range(8):
        eng.do_decode_step()
        if done.done:
            break
    toks = done.wait(timeout=5)
    assert len(toks) == 4
    assert all(0 <= t < model.cfg.vocab_size for t in toks)


def test_engine_greedy_matches_sequential_decode():
    """Continuous batching must not change greedy outputs vs a plain
    prefill+decode loop on the same model."""
    model, params = _tiny_model()
    P = 16
    scfg = ServeConfig(max_batch=2, max_len=96, prefill_bucket=P,
                       max_new_tokens=4)
    prompt = (np.arange(8, dtype=np.int32) * 7 + 3) % model.cfg.vocab_size

    # engine path
    eng = InferenceEngine(model, params, scfg)
    done = eng.submit(prompt)
    eng.do_prefill(eng.admit_one()[0])
    while not done.done:
        eng.do_decode_step()
    engine_tokens = done.wait(timeout=5)

    # reference path: same padded prompt, manual greedy decode
    import jax.numpy as jnp
    padded = np.zeros((1, P), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, cache = jax.jit(model.prefill)(params, {"tokens": padded})
    cache = jax.tree.map(
        lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, 96 - x.shape[2])]
                          + [(0, 0)] * (x.ndim - 3)) if x.ndim >= 3 else x,
        cache)
    toks = [int(np.argmax(np.asarray(logits)[0]))]
    pos = P
    for _ in range(3):
        lg, cache = jax.jit(model.decode_step)(
            params, cache, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        toks.append(int(np.argmax(np.asarray(lg)[0])))
        pos += 1
    assert engine_tokens == toks


@pytest.mark.parametrize("backend", BACKENDS)
def test_llm_app_end_to_end(backend):
    model, params = _tiny_model()
    scfg = ServeConfig(max_batch=2, max_len=64, prefill_bucket=16,
                       max_new_tokens=4)
    app = build_llm_app(model, params, scfg, backend=backend)
    with app:
        app.send("engine", "run", None)      # launch driver
        futs = [app.send("api", "generate",
                         {"text": f"hello world {i}", "max_new": 4})
                for i in range(4)]
        outs = [f.wait(timeout=60) for f in futs]
        for out in outs:
            assert len(out["tokens"]) == 4
            assert isinstance(out["text"], str)
        app.services["engine"].state["stop"] = True


def test_continuous_batching_concurrency():
    """More requests than slots: all complete, slots are recycled."""
    model, params = _tiny_model()
    scfg = ServeConfig(max_batch=2, max_len=64, prefill_bucket=16,
                       max_new_tokens=3)
    app = build_llm_app(model, params, scfg, backend="fiber")
    with app:
        app.send("engine", "run", None)
        futs = [app.send("api", "generate", {"text": f"req {i}"})
                for i in range(6)]
        outs = [f.wait(timeout=120) for f in futs]
        assert all(len(o["tokens"]) == 3 for o in outs)
        eng = app.services["engine"].state["engine"]
        assert eng.generated >= 6 * 2
        app.services["engine"].state["stop"] = True


def test_engine_ssm_family():
    """Recurrent family (rwkv6) serves through the same engine."""
    model, params = _tiny_model("rwkv6-3b")
    scfg = ServeConfig(max_batch=2, max_len=64, prefill_bucket=16,
                       max_new_tokens=3)
    eng = InferenceEngine(model, params, scfg)
    done = eng.submit(np.arange(8, dtype=np.int32))
    eng.do_prefill(eng.admit_one()[0])
    while not done.done:
        eng.do_decode_step()
    assert len(done.wait(timeout=5)) == 3


class _DeviceFault(RuntimeError):
    """Stands in for an error the device raises (compile, out of memory)."""


@pytest.mark.parametrize("backend", BACKENDS)
def test_device_error_reaches_the_client(backend):
    """A device error in the engine fails every request with that error
    within seconds (not the client's timeout), and later ones at once."""
    model, params = _tiny_model()
    scfg = ServeConfig(max_batch=2, max_len=64, prefill_bucket=16,
                       max_new_tokens=4)
    app = build_llm_app(model, params, scfg, backend=backend)

    def broken_prefill(req):
        raise _DeviceFault("prefill failed on the device")

    app.state["engine"].do_prefill = broken_prefill
    with app:
        run = app.send("engine", "run", None)
        t0 = time.perf_counter()
        futs = [app.send("api", "generate", {"text": f"req {i}"})
                for i in range(3)]          # more requests than slots
        for fut in futs:
            with pytest.raises(_DeviceFault):
                fut.wait(timeout=10)
        assert time.perf_counter() - t0 < 5
        with pytest.raises(_DeviceFault):
            run.wait(timeout=10)
        with pytest.raises(_DeviceFault):
            app.send("api", "generate", {"text": "later"}).wait(timeout=10)
