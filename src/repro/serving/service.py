"""Model server as a microservice graph on the async-RPC runtime.

    api ──async──> tokenizer          (CPU-side text work)
     │
     └──async──> engine.submit       (parks until generation completes)
    engine driver fiber: admit -> prefill -> continuous decode steps
                        (device work via Offload; never blocks the scheduler)

Under the paper's baseline ("thread") every submit is a blocked kernel
thread and every async call spawns one more; under "fiber" they are parked
fibers on one scheduler — the DeathStarBench contrast, applied to an LLM
server.

Spans (``repro.core.instrument``; recorded by ``spans.SpanRecorder``): per
request ``api.generate`` with ``api.tokenize``, ``api.engine`` and
``api.detokenize``, each from its ``AsyncRpc`` to the end of its ``Wait``,
all under the request's trace id, which travels to ``engine.submit`` in
the payload; in the engine driver ``engine.offload`` around each hand-off
to the offload pool and ``engine.idle_sleep`` around each idle sleep.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from ..core import (App, AsyncRpc, Compute, CurrentContext, Offload,
                    ServiceSpec, Sleep, Wait, instrument)
from ..core.context import trace_ticket
from .engine import InferenceEngine, ServeConfig

IDLE_SLEEP = 0.002


def tokenize(text: str, vocab_size: int) -> np.ndarray:
    """Toy tokenizer: one token id per UTF-8 byte."""
    ids = np.frombuffer(text.encode("utf-8"), dtype=np.uint8).astype(np.int32)
    return ids % vocab_size


def _tokenize(svc: Any, payload: Any):
    """Tokenizer service (real CPU work)."""
    yield Compute(5e-6)
    return {"ids": tokenize(payload["text"], svc.state["vocab_size"])}


def _detokenize(svc: Any, payload: Any):
    yield Compute(5e-6)
    return {"text": " ".join(str(t) for t in payload["ids"])}


def _generate(svc: Any, payload: Any):
    """API front: tokenize + submit (async), then detokenize the result.
    Its spans' id is the caller's trace id, else a fresh one."""
    rid = None
    h = instrument.hooks
    if h is not None:
        ctx = yield CurrentContext()
        rid = trace_ticket() if ctx is None else ctx.trace_id
        h.span_begin("api.generate", rid)
        h.span_begin("api.tokenize", rid)
    f_tok = yield AsyncRpc("tokenizer", "tokenize", payload)
    tok = yield Wait(f_tok)
    if h is not None:
        h.span_end("api.tokenize", rid, None)
        h.span_begin("api.engine", rid)
    f_gen = yield AsyncRpc("engine", "submit",
                           {"ids": tok["ids"],
                            "max_new": payload.get("max_new"), "rid": rid})
    gen = yield Wait(f_gen)
    if h is not None:
        h.span_end("api.engine", rid, None)
        h.span_begin("api.detokenize", rid)
    f_det = yield AsyncRpc("detokenizer", "detokenize", gen)
    det = yield Wait(f_det)
    if h is not None:
        h.span_end("api.detokenize", rid, None)
        h.span_end("api.generate", rid, None)
    return {"text": det["text"], "tokens": gen["ids"]}


def _submit(svc: Any, payload: Any):
    """Parks (fiber) / blocks (thread) until the engine finishes the request
    — the paper's wait-dominated async pattern."""
    engine: InferenceEngine = svc.state["engine"]
    ids, max_new = payload["ids"], payload.get("max_new")
    rid = payload.get("rid")
    # the two-argument call unless a trace id came: a wrapper of the
    # two-argument ``submit`` (the chip benchmark's probe) keeps working
    done = (engine.submit(ids, max_new) if rid is None
            else engine.submit(ids, max_new, rid))
    tokens = yield Wait(done)
    return {"ids": tokens}


def _run(svc: Any, payload: Any):
    """The engine driver: a single long-lived fiber.  If device work raises
    (a compile or out-of-memory error), every request in the engine fails
    with that error at once instead of waiting out its client's timeout."""
    engine: InferenceEngine = svc.state["engine"]
    try:
        while not svc.state.get("stop"):
            progressed = False
            admitted = engine.admit_one()
            h = instrument.hooks
            if admitted is not None:
                req = admitted[0]
                if h is not None:
                    h.span_begin("engine.offload", None)
                yield Wait((yield Offload(engine.do_prefill, (req,))))
                if h is not None:
                    h.span_end("engine.offload", None, None)
                progressed = True
            if h is not None:
                h.span_begin("engine.offload", None)
            finished = yield Wait((yield Offload(engine.do_decode_step)))
            if h is not None:
                h.span_end("engine.offload", None, None)
            if finished:
                progressed = True
            if not progressed and not engine.has_work():
                if h is not None:
                    h.span_begin("engine.idle_sleep", None)
                yield Sleep(IDLE_SLEEP)
                if h is not None:
                    h.span_end("engine.idle_sleep", None, None)
    except Exception as exc:
        engine.fail_all(exc)
        raise
    return "stopped"


def build_llm_app(model, params, scfg: Optional[ServeConfig] = None,
                  backend: str = "fiber") -> App:
    """Wire the LLM server; call ``app.send('engine', 'run', None)`` once
    after ``app.start()`` to launch the driver."""
    scfg = scfg or ServeConfig()
    engine = InferenceEngine(model, params, scfg)
    app = App(backend=backend, offload_threads=2)
    app.add_service(ServiceSpec(
        "api", {"generate": _generate}, n_workers=2))
    app.add_service(ServiceSpec(
        "tokenizer", {"tokenize": _tokenize}, n_workers=1,
        state={"vocab_size": model.cfg.vocab_size}))
    app.add_service(ServiceSpec(
        "detokenizer", {"detokenize": _detokenize}, n_workers=1))
    app.add_service(ServiceSpec(
        "engine", {"submit": _submit, "run": _run}, n_workers=2,
        state={"engine": engine}))
    app.state = {"engine": engine}  # type: ignore[attr-defined]
    return app
