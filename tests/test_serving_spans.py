"""The LLM server's spans: one trace id from the api down to the engine's
first token, a decode step covered by its children, nothing recorded with
no hook table, and collections recorded as ``host.gc``."""
import gc
import math

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import RequestContext, instrument
from repro.models import Model
from repro.serving import ServeConfig, build_llm_app
from repro.serving.engine import InferenceEngine
from repro.serving.spans import SpanRecorder

BACKENDS = ("fiber", "thread")
DECODE_CHILDREN = ["decode.prepare", "decode.dispatch", "decode.device_wait",
                   "decode.fetch", "decode.sample", "decode.commit"]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("qwen2-0.5b").with_(remat=False)
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture
def recorder():
    rec = SpanRecorder()
    rec.install()
    try:
        yield rec
    finally:
        rec.uninstall()
    assert instrument.hooks is None


def _engine(tiny, max_new=4):
    model, params = tiny
    return InferenceEngine(model, params, ServeConfig(
        max_batch=2, max_len=64, prefill_bucket=16, max_new_tokens=max_new))


def _drive(eng, prompts):
    done = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    while not all(d.done for d in done):
        adm = eng.admit_one()
        if adm is not None:
            eng.do_prefill(adm[0])
        eng.do_decode_step()
    return [d.wait(timeout=5) for d in done]


def _inside(child, parent):
    return parent.start <= child.start <= child.end <= parent.end


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("with_context", [False, True])
def test_request_spans_share_one_rid_and_nest(tiny, recorder, backend,
                                              with_context):
    model, params = tiny
    app = build_llm_app(model, params, ServeConfig(
        max_batch=2, max_len=64, prefill_bucket=16, max_new_tokens=3),
        backend=backend)
    ctx = RequestContext() if with_context else None
    with app:
        app.send("engine", "run", None)
        out = app.send("api", "generate", {"text": "hello", "max_new": 3},
                       ctx=ctx).wait(timeout=60)
        app.services["engine"].state["stop"] = True
    assert len(out["tokens"]) == 3
    gen = [s for s in recorder.spans if s.name == "api.generate"]
    assert len(gen) == 1
    rid = gen[0].rid
    if with_context:
        assert rid == ctx.trace_id
    mine = {s.name: s for s in recorder.spans if s.rid == rid}
    assert set(mine) == {"api.generate", "api.tokenize", "api.engine",
                         "api.detokenize", "engine.request", "engine.queued",
                         "engine.first_token"}
    tree = {"api.tokenize": "api.generate", "api.engine": "api.generate",
            "api.detokenize": "api.generate", "engine.request": "api.engine",
            "engine.queued": "engine.request",
            "engine.first_token": "engine.request"}
    for child, parent in tree.items():
        assert mine[child].parent == mine[parent].id
        assert _inside(mine[child], mine[parent])
    assert mine["engine.first_token"].end == mine["engine.first_token"].start
    assert mine["api.tokenize"].end <= mine["api.engine"].start
    assert mine["api.engine"].end <= mine["api.detokenize"].start
    # the engine loop's spans carry no request's id
    loop = {s.name for s in recorder.spans if s.rid is None}
    assert {"engine.offload", "engine.prefill", "engine.decode_step"} <= loop


def test_decode_step_children_cover_the_step(tiny, recorder):
    eng = _engine(tiny)
    _drive(eng, [np.arange(5), np.arange(9)])
    steps = [s for s in recorder.spans if s.name == "engine.decode_step"
             and s.attrs["active"] > 0]
    assert len(steps) == eng.steps
    for st in steps:
        kids = [s for s in recorder.spans if s.parent == st.id]
        assert [k.name for k in kids] == DECODE_CHILDREN
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.start
        assert st.start <= kids[0].start and kids[-1].end <= st.end
        covered = sum(k.end - k.start for k in kids)
        assert covered >= 0.5 * (st.end - st.start)
        waits = [s for s in recorder.spans if s.name == "engine.lock_wait"
                 and s.parent in (kids[0].id, kids[-1].id)]
        assert len(waits) == 2
    for pre in (s for s in recorder.spans if s.name == "engine.prefill"):
        kids = [s.name for s in recorder.spans if s.parent == pre.id]
        assert kids == ["prefill.dispatch", "prefill.fetch",
                        "prefill.sample"]
    assert all(s.end >= s.start for s in recorder.spans)


def test_step_attrs_count_slots_context_and_real_tokens(tiny, recorder):
    eng = _engine(tiny, max_new=3)
    P = eng.scfg.prefill_bucket
    _drive(eng, [np.arange(5), np.arange(9)])
    steps = [s.attrs for s in recorder.spans
             if s.name == "engine.decode_step" and s.attrs["active"] > 0]
    first = steps[0]
    # one slot decodes its first token before the second is prefilled
    assert first == {"step": 1, "active": 1, "ctx": P + 1, "real": 5 + 1,
                     "pending": 1}
    assert [a["step"] for a in steps] == list(range(1, len(steps) + 1))
    for a in steps:
        assert a["real"] <= a["ctx"]


def test_nothing_recorded_without_hooks(tiny):
    rec = SpanRecorder()                     # made, never installed
    assert instrument.hooks is None
    eng = _engine(tiny)
    outs = _drive(eng, [np.arange(5)])
    assert len(outs[0]) == 4 and rec.spans == []


def test_sanitizer_style_hooks_see_no_spans(tiny):
    """A hook table that overrides no span method (the sanitizer's case)
    serves as before: the span calls fall to the base class's no-ops."""
    instrument.install(instrument.Hooks())
    try:
        outs = _drive(_engine(tiny), [np.arange(5)])
    finally:
        instrument.uninstall()
    assert len(outs[0]) == 4


def test_collections_are_host_gc_spans(recorder):
    gc.collect()
    gcs = [s for s in recorder.spans if s.name == "host.gc"]
    assert gcs and gcs[-1].attrs == {"generation": 2}
    assert all(s.end >= s.start for s in gcs)
    recorder.uninstall()
    n = len(recorder.spans)
    gc.collect()
    assert len(recorder.spans) == n


def test_an_end_with_no_begin_is_ignored(recorder):
    recorder.span_end("engine.queued", 7, None)
    recorder.span_begin("x", 7)
    recorder.event("e", 7, {"k": 1})
    recorder.span_end("x", 7, {"n": 2})
    x, e = [s for s in recorder.spans if s.rid == 7]
    assert (x.name, x.attrs, e.parent) == ("x", {"n": 2}, x.id)
    assert not math.isnan(x.end)
