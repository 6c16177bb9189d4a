"""Serving driver: ``python -m repro.launch.serve --arch qwen2-0.5b --smoke``

Boots the microservice LLM server (api -> tokenizer -> engine ->
detokenizer) on the chosen async backend and runs a batch of requests
through it, reporting throughput and latency percentiles together with the
device they ran on.  :func:`serve` is the serving loop itself, and
``chip_smoke.py`` drives the same function.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, List, Optional

import jax
import numpy as np

from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..models import Model
from ..serving import InferenceEngine, ServeConfig, build_llm_app
from .compile_cache import enable_compile_cache


@dataclass
class ServeReport:
    """What one :func:`serve` call sent and got back, request by request."""

    backend: str
    texts: List[str]
    tokens: List[Optional[List[int]]]    # None where the request failed
    errors: List[BaseException]
    latencies: List[float]               # seconds, answered requests only
    warmup_s: float                      # first request, compiles included
    wall_s: float
    driver_error: Optional[BaseException]
    engine: InferenceEngine

    @property
    def sent(self) -> int:
        return len(self.texts)

    @property
    def answered(self) -> int:
        return sum(t is not None for t in self.tokens)

    @property
    def failed(self) -> int:
        return len(self.errors)


TIMEOUT_S = 600.0      # per wait; a device error fails the request sooner


def serve(model: Model, params: Any, scfg: ServeConfig, backend: str,
          texts: List[str]) -> ServeReport:
    """Serve ``texts`` through ``build_llm_app``: start the engine driver,
    send one warm-up request, then every text at once, and wait for all.

    A failed request is recorded, not raised; the driver is stopped before
    returning, and its own error (if it died) is in ``driver_error``."""
    app = build_llm_app(model, params, scfg, backend=backend)
    engine = app.state["engine"]
    with app:
        run = app.send("engine", "run", None)
        t0 = time.perf_counter()
        app.send("api", "generate", {"text": "warmup"}).wait(timeout=TIMEOUT_S)
        warmup_s = time.perf_counter() - t0
        lats: List[float] = []

        def timed(fut: Any, ts: float) -> None:
            if fut.exception() is None:
                lats.append(time.perf_counter() - ts)

        futs = []
        t0 = time.perf_counter()
        for text in texts:
            ts = time.perf_counter()
            fut = app.send("api", "generate", {"text": text})
            fut.add_done_callback(lambda f, ts=ts: timed(f, ts))
            futs.append(fut)
        tokens: List[Optional[List[int]]] = []
        errors: List[BaseException] = []
        for fut in futs:
            try:
                tokens.append(list(fut.wait(timeout=TIMEOUT_S)["tokens"]))
            except Exception as exc:
                tokens.append(None)
                errors.append(exc)
        wall_s = time.perf_counter() - t0
        app.services["engine"].state["stop"] = True
        try:
            run.wait(timeout=TIMEOUT_S)
            driver_error = None
        except Exception as exc:
            driver_error = exc
    return ServeReport(backend, list(texts), tokens, errors, lats, warmup_s,
                       wall_s, driver_error, engine)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", default="fiber",
                    choices=("fiber", "thread"),
                    help="async-RPC backend (the paper's comparison)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg.with_(remat=False))
    params = model.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_batch=args.max_batch, max_len=128,
                       prefill_bucket=32, max_new_tokens=args.max_new)
    rep = serve(model, params, scfg, args.backend,
                [f"request {i}" for i in range(args.requests)])
    dev = jax.devices()[0]
    tokens = sum(len(t) for t in rep.tokens if t is not None)
    print(f"device={dev.platform}/{dev.device_kind} backend={args.backend} "
          f"requests={rep.sent} answered={rep.answered} "
          f"failed={rep.failed} wall={rep.wall_s:.2f}s "
          f"rps={rep.answered / rep.wall_s:.1f} tokens={tokens} "
          f"tok/s={tokens / rep.wall_s:.1f}")
    if rep.latencies:
        print(f"latency p50={np.percentile(rep.latencies, 50) * 1e3:.1f}ms "
              f"p99={np.percentile(rep.latencies, 99) * 1e3:.1f}ms")
    first_error = rep.driver_error or (rep.errors[0] if rep.errors else None)
    if first_error is not None:
        print(f"error: {type(first_error).__name__}: {first_error}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
