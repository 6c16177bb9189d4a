"""Serving engine integration tests: continuous batching over the fiber
(and baseline thread) runtimes with a tiny model."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model
from repro.serving import ServeConfig, build_llm_app
from repro.serving.engine import InferenceEngine, _greedy_pick
from test_chip_smoke import chip_smoke

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


@pytest.mark.parametrize("rows", [1, 5])
def test_greedy_pick_matches_np_argmax(rows):
    """The device pick is ``np.argmax`` per row: ties go to the lowest id,
    ``-inf`` entries are never picked unless the whole row is ``-inf``."""
    V = 37
    rng = np.random.default_rng(rows)
    logits = rng.standard_normal((rows, V)).astype(np.float32)
    logits[0, [4, 9, 30]] = 7.0                    # a three-way tie
    logits[0, [0, 1, 2]] = -np.inf
    if rows > 1:
        logits[1, :] = -np.inf                     # nothing but -inf
        logits[2, :] = 0.5                         # every id ties
        logits[3, [V - 1, 11]] = 9.0               # tie, out of order
        logits[4, ::2] = -np.inf
    picks = jax.jit(_greedy_pick)(jnp.asarray(logits))
    assert picks.dtype == jnp.int32 and picks.shape == (rows,)
    np.testing.assert_array_equal(np.asarray(picks), np.argmax(logits, -1))
    assert int(picks[0]) == 4


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "minicpm3-4b",
                                  "deepseek-v2-lite"])
def test_served_tokens_equal_host_argmax_of_decode_logits(arch):
    """Tokens picked on the device equal a host argmax over ``_decode``'s
    logits (``chip_smoke.greedy_reference``: each request alone in slot 0),
    with two of four slots active and, once the shorter request ends, three
    slots padded.  The engine serves through its donating programs (the
    insert and ``_decode_step``), the reference through the plain
    ``_decode`` on a cache of its own."""
    model, params = _tiny_model(arch)
    # At init the blocks barely move the residual, and qwen2's tied head
    # then echoes the last (pad) token; larger weights give each request
    # tokens of its own, so a pick read from the wrong slot shows.
    params = {**params, "blocks": jax.tree.map(
        lambda w: w * 8 if w.ndim == 3 else w, params["blocks"])}
    scfg = ServeConfig(max_batch=4, max_len=64, prefill_bucket=16,
                       max_new_tokens=6)
    V = model.cfg.vocab_size
    prompts = [(np.arange(9, dtype=np.int32) * 5 + 1) % V,
               (np.arange(13, dtype=np.int32) * 11 + 2) % V]
    lengths = [6, 3]
    eng = InferenceEngine(model, params, scfg)
    dones = [eng.submit(p, max_new=n) for p, n in zip(prompts, lengths)]
    for _ in prompts:
        eng.do_prefill(eng.admit_one()[0])
    while not all(d.done for d in dones):
        eng.do_decode_step()
    served = [d.wait(timeout=5) for d in dones]
    assert served[0][:3] != served[1]
    assert served == [chip_smoke.greedy_reference(eng, p, n)[0]
                      for p, n in zip(prompts, lengths)]


def _forced_token(decode):
    """A step whose logits pick token 7 in every slot."""
    def step(params, cache, toks, pos):
        logits, cache = decode(params, cache, toks, pos)
        return jnp.zeros_like(logits).at[:, 7].set(1.0), cache
    return step


def _unchanged_state(decode):
    """A step that returns the cache it was given (no new entries)."""
    return lambda params, cache, toks, pos: (
        decode(params, cache, toks, pos)[0], cache)


@pytest.mark.parametrize("wrapper", [_forced_token, _unchanged_state])
def test_wrapper_set_on_decode_decides_what_is_served(wrapper):
    """A wrapper set on ``engine._decode`` after construction, as the chip
    benchmark plants its faults, is what the donating decode program runs:
    forced tokens are served, and a step that hands back the cache it was
    given serves the tokens of a cache that never moves past the insert,
    on live buffers."""
    model, params = _tiny_model()
    P, n_new = 16, 5
    scfg = ServeConfig(max_batch=2, max_len=64, prefill_bucket=P,
                       max_new_tokens=n_new)
    prompt = (np.arange(8, dtype=np.int32) * 7 + 3) % model.cfg.vocab_size
    eng = InferenceEngine(model, params, scfg)
    plain = eng._decode
    eng._decode = wrapper(plain)
    done = eng.submit(prompt)
    eng.do_prefill(eng.admit_one()[0])
    inserted = jax.tree.map(np.asarray, eng.cache)
    while not done.done:
        eng.do_decode_step()
    served = done.wait(timeout=5)
    if wrapper is _forced_token:
        assert served[1:] == [7] * (n_new - 1)
    else:
        want = [served[0]]
        for i in range(n_new - 1):
            toks = np.zeros((2, 1), np.int32)
            toks[1, 0] = want[-1]
            pos = np.array([0, P + i], np.int32)
            logits, _ = plain(params, inserted, jnp.asarray(toks),
                              jnp.asarray(pos))
            want.append(int(np.argmax(np.asarray(logits)[1])))
        assert served == want
        for a, b in zip(jax.tree.leaves(eng.cache),
                        jax.tree.leaves(inserted)):
            np.testing.assert_array_equal(np.asarray(a), b)


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
