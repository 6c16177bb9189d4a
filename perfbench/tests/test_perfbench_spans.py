"""The program's spans as the benchmark reads them: the mirror on a profiler
trace, idle gaps named by the innermost engine span, the span readers on
records built by hand, the engine's step numbers against ``Probe``'s, and
``spanrun.py`` end to end on the CPU at smoke size."""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from perfbench import devtrace as dt
from perfbench import harness, spanreport
from perfbench import spans as sp
from perfbench.devtrace import Event, Trace
from repro.configs import get_smoke_config
from repro.models import Model
from repro.serving import ServeConfig
from repro.serving.engine import InferenceEngine
from repro.serving.spans import Span, SpanRecorder


@pytest.fixture(scope="module")
def engine_parts():
    cfg = get_smoke_config("qwen2-0.5b").with_(remat=False)
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def engine(parts, max_new=5):
    model, params = parts
    return InferenceEngine(model, params, ServeConfig(
        max_batch=3, max_len=64, prefill_bucket=16, max_new_tokens=max_new))


def drive(eng, prompts):
    done = [eng.submit(np.asarray(p, np.int32)) for p in prompts]
    while not all(d.done for d in done):
        adm = eng.admit_one()
        if adm is not None:
            eng.do_prefill(adm[0])
        eng.do_decode_step()


@pytest.fixture
def recorder():
    rec = SpanRecorder()
    rec.install()
    try:
        yield rec
    finally:
        rec.uninstall()


def test_step_numbers_equal_probes(engine_parts, recorder):
    eng = engine(engine_parts)
    probe = harness.Probe(eng, traced=True)
    drive(eng, [np.arange(3), np.arange(11), np.arange(7)])
    mine = [(s.attrs["active"], s.attrs["ctx"]) for s in recorder.spans
            if s.name == "engine.decode_step" and s.attrs["active"] > 0]
    assert mine == [(d["active"], d["ctx"]) for d in probe.decodes]
    assert len(mine) == eng.steps


def test_mirrored_spans_come_back_on_the_trace(engine_parts, recorder,
                                               tmp_path):
    eng = engine(engine_parts)
    drive(eng, [np.arange(4)])                   # compiled before tracing
    n0 = len(recorder.spans)
    jax.profiler.start_trace(str(tmp_path))
    drive(eng, [np.arange(6)])
    jax.profiler.stop_trace()
    want = [s for s in recorder.spans[n0:]
            if s.end > s.start and s.name != sp.GC_SPAN]
    got = [s for s in sp.load_spans(str(tmp_path)) if s.name != sp.GC_SPAN]
    assert [s.name for s in got] == [s.name for s in want]
    for g, w in zip(got, want):
        assert abs((g.end - g.start) / 1e6 - (w.end - w.start) * 1e3) < 0.2
    # the nesting on the trace's clock is the recorded one: each span's
    # shortest holder in time, among the request's spans or among the
    # engine loop's, is its recorded parent
    by_id = {s.id: s.name for s in want}
    for i, g in enumerate(got):
        holders = [h for j, h in enumerate(got) if j != i
                   and h.start <= g.start and g.end <= h.end
                   and h.end - h.start > g.end - g.start
                   and sp.on_engine_path(h.name) == sp.on_engine_path(g.name)]
        inner = min(holders, key=lambda h: h.end - h.start, default=None)
        assert (inner.name if inner else None) == by_id.get(want[i].parent)


def test_idle_gaps_named_by_the_innermost_span_and_gc_wins():
    # window 0..100; device busy 10-20 and 60-70
    ops = [Event("jit_decode_step/fusion.1", 10, 20),
           Event("jit_decode_step/fusion.1", 60, 70)]
    spans = [Event("engine.offload", 0, 95),
             Event("engine.decode_step", 8, 90),
             Event("decode.dispatch", 8, 10),
             Event("decode.device_wait", 10, 22),
             Event("decode.fetch", 22, 40),
             Event("decode.sample", 40, 55),
             Event("host.gc", 45, 58),
             Event("api.generate", 0, 100)]     # a request's: not the engine's
    tr = Trace(window=(0, 100), ops={"/device:TPU:0": ops},
               spans=sp.name_idle(spans, (0, 100)))
    got = dict(x for x in dt.breakdown(tr, top=20)["idle_gaps"]
               if x[0].endswith("(total)"))
    # gaps: 0-10 (mid 5), 20-60 (mid 40), 70-100 (mid 85)
    assert got == {
        "engine.offload (total)": pytest.approx(10e-9),
        "decode.sample (total)": pytest.approx(40e-9),
        "engine.decode_step (total)": pytest.approx(30e-9)}
    # split by what covers each instant: 0-8 offload, 8-10 dispatch; 20-22
    # device_wait, 22-40 fetch, 40-45 sample, 45-58 gc, 58-60 decode_step;
    # 70-90 decode_step, 90-95 offload, 95-100 nothing
    split = sp.idle_split_s(tr, tr.spans)
    assert split == {
        "engine.offload": pytest.approx(13e-9),
        "decode.dispatch": pytest.approx(2e-9),
        "decode.device_wait": pytest.approx(2e-9),
        "decode.fetch": pytest.approx(18e-9),
        "decode.sample": pytest.approx(5e-9),
        "host.gc": pytest.approx(13e-9),
        "engine.decode_step": pytest.approx(22e-9),
        dt.ENGINE_IDLE: pytest.approx(5e-9)}
    names = [(s.name, s.start, s.end) for s in tr.spans]
    assert ("host.gc", 45, 58) in names
    assert ("decode.sample", 40, 45) in names
    assert ("decode.dispatch", 8, 10) in names
    assert ("engine.offload", 90, 95) in names
    assert names[-1] == ("engine.offload", 90, 95)
    assert all(a[2] <= b[1] for a, b in zip(names, names[1:]))


def hand_spans():
    """Two requests and two decode steps, on the perf_counter clock."""
    S = Span
    return [
        S(0, "api.generate", 1, None, 0.0, 2.0),
        S(1, "engine.request", 1, 0, 0.1, 1.9),
        S(2, "engine.queued", 1, 1, 0.1, 0.4),
        S(3, "api.generate", 2, None, 0.5, 3.5),
        S(4, "engine.request", 2, 3, 0.6, 3.0),
        S(5, "engine.queued", 2, 4, 0.6, 1.6),
        S(6, "engine.decode_step", None, None, 1.0, 1.05,
          {"step": 1, "active": 2, "ctx": 40, "real": 20, "pending": 0}),
        S(7, "decode.device_wait", None, 6, 1.01, 1.03),
        S(8, "engine.decode_step", None, None, 1.1, 1.14,
          {"step": 2, "active": 2, "ctx": 42, "real": 22, "pending": 0}),
        S(9, "decode.device_wait", None, 8, 1.11, 1.12),
        S(10, "decode.fetch", None, 8, 1.12, 1.13),
        S(11, "engine.decode_step", None, None, 1.2, 1.21, {"active": 0}),
        S(12, "engine.first_token", 1, 1, 0.5, 0.5),
    ]


def test_span_readers_on_records_built_by_hand():
    spans = hand_spans()
    # admitted in [0, 2): both requests; nearest-rank p95 of 300, 1000 ms
    assert sp.queue_wait_p95_ms(spans, 0, 2) == pytest.approx(1000.0)
    assert sp.queue_wait_p95_ms(spans, 0, 1) == pytest.approx(300.0)
    # steps with a slot decoding: 50 - 20 and 40 - 10 ms of host time
    assert sp.step_host_ms(spans, 0, 2) == pytest.approx(30.0)
    # answered in [0, 4): 2.0 - 1.8 and 3.0 - 2.4 s
    assert sp.rpc_hops_p95_ms(spans, 0, 4) == pytest.approx(600.0)
    assert sp.rpc_hops_p95_ms(spans, 0, 3) == pytest.approx(200.0)
    assert sp.kv_used_share(spans, 0, 2, 2, 50) == pytest.approx(21.0)
    split = sp.step_split_s(spans, 0, 2)
    assert split["decode.device_wait"] == pytest.approx(0.03)
    assert split["decode.fetch"] == pytest.approx(0.01)
    assert split["engine.decode_step (self)"] == pytest.approx(0.05)
    assert sp.first_token_p95_ms(spans, 0, 2) == pytest.approx(500.0)
    assert sp.step_means(spans, 0, 2) == {"active": 2, "ctx": 41,
                                          "pending": 0}
    hops = sp.hops_p95_ms(spans + [
        Span(13, "api.tokenize", 1, 0, 0.0, 0.05),
        Span(14, "api.engine", 1, 0, 0.05, 1.95),
        Span(15, "api.detokenize", 1, 0, 1.95, 1.99)], 0, 3)
    assert hops == {"api.tokenize": pytest.approx(50.0),
                    "submit": pytest.approx(100.0),
                    "api.detokenize": pytest.approx(40.0)}
    for read in (sp.queue_wait_p95_ms, sp.step_host_ms, sp.rpc_hops_p95_ms):
        assert read(spans, 10, 11) is None
    assert sp.kv_used_share(spans, 10, 11, 2, 50) is None


def test_spanrun_reports_the_span_metrics_at_smoke_size(tree):
    root, base = tree
    res = spanreport.execute("qwen2-smoke-chat", 2**31 + 3, 1.0, True,
                             time.perf_counter(), allow_cpu=True, root=root,
                             base=base)
    m = res["metrics"]
    assert res["failed"] == 0 and res["tokens_per_s"] > 0
    assert m["engine.queue_wait_p95_ms"] >= 0
    assert m["engine.step_host_ms"] > 0
    assert m["rpc.hops_p95_ms"] > 0
    assert 0 < m["engine.kv_used_share"] <= 100
    assert res["decode_steps"] > 0
    assert set(res["decode_split_s"]) == {
        "decode.prepare", "decode.dispatch", "decode.device_wait",
        "decode.fetch", "decode.sample", "decode.commit",
        "engine.decode_step (self)"}
    assert all(v > 0 for v in res["hops_p95_ms"].values())
    assert res["first_token_p95_ms"] > 0
    assert res["step_means"]["active"] >= 1
    assert res["trace"]["engine.step_host_ms"] > 0       # the CPU's trace
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(res)


def test_spanrun_refuses_the_cpu():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "perfbench/spanrun.py", "--workload", "qwen2-chat",
         "--seed", "1", "--seconds", "1", "--profile", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2
    assert "no accelerator" in p.stderr
