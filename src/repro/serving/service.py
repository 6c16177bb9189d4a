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
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from ..core import (App, AsyncRpc, Compute, Offload, ServiceSpec, Sleep,
                    Wait, WaitAll)
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
    """API front: tokenize + submit (async), then detokenize the result."""
    f_tok = yield AsyncRpc("tokenizer", "tokenize", payload)
    tok = yield Wait(f_tok)
    f_gen = yield AsyncRpc("engine", "submit",
                           {"ids": tok["ids"],
                            "max_new": payload.get("max_new")})
    gen = yield Wait(f_gen)
    f_det = yield AsyncRpc("detokenizer", "detokenize", gen)
    det = yield Wait(f_det)
    return {"text": det["text"], "tokens": gen["ids"]}


def _submit(svc: Any, payload: Any):
    """Parks (fiber) / blocks (thread) until the engine finishes the request
    — the paper's wait-dominated async pattern."""
    engine: InferenceEngine = svc.state["engine"]
    done = engine.submit(payload["ids"], payload.get("max_new"))
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
            if admitted is not None:
                req = admitted[0]
                yield Wait((yield Offload(engine.do_prefill, (req,))))
                progressed = True
            finished = yield Wait((yield Offload(engine.do_decode_step)))
            if finished:
                progressed = True
            if not progressed and not engine.has_work():
                yield Sleep(IDLE_SLEEP)
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
