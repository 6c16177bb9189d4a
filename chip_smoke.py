"""Bring-up check of the served LLM path on one TPU chip.

    python chip_smoke.py

Serves qwen2-0.5b at its published widths (24 layers, d_model 896, 14 q /
2 kv heads, vocab 151,936; random weights from ``PRNGKey(0)``) through the
RPC graph of ``repro.serving.build_llm_app`` (api -> tokenizer -> engine ->
detokenizer), first on the ``fiber`` backend and then on ``thread``: one
warm-up request, then 16 concurrent requests with distinct seeded prompts of
varied length.  For every answer it checks that

* the request resolved with ``max_new_tokens`` tokens;
* the served tokens equal a greedy loop that runs the request alone through
  the engine's own jitted ``prefill`` and ``decode_step`` on the same padded
  prompt (slot insertion and mixed-position batched decode), and that loop's
  logits are finite;
* the params and every engine cache leaf sit on the device;
* the engine driver did not fail.

The last line of standard output is ``{"ok": true, "device": {...}}``.  With
no TPU, or when any check fails, the script exits non-zero and prints no
such line.  The compile cache is ``JAX_COMPILATION_CACHE_DIR`` where that is
set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import List, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import (enable_compile_cache,  # noqa: E402
                                        watch_compiles)
from repro.launch.serve import ServeReport, serve  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.serving import InferenceEngine, ServeConfig  # noqa: E402
from repro.serving.service import tokenize  # noqa: E402

ARCH = "qwen2-0.5b"
SERVE = ServeConfig(max_batch=8, max_len=2048, prefill_bucket=128,
                    max_new_tokens=32)
BACKENDS = ("fiber", "thread")
N_REQUESTS = 16
SEED = 0


class SmokeFailure(AssertionError):
    """A check of the served answers failed."""


def make_prompts(n: int, max_chars: int) -> List[str]:
    """``n`` distinct printable-ASCII prompts of 8..max_chars characters
    (one token per character), drawn from ``SEED``."""
    rng = np.random.default_rng(SEED)
    texts: List[str] = []
    while len(texts) < n:
        length = int(rng.integers(8, max_chars + 1))
        text = "".join(map(chr, rng.integers(32, 127, length)))
        if text not in texts:
            texts.append(text)
    return texts


def greedy_reference(engine: InferenceEngine, ids: np.ndarray, n_new: int
                     ) -> Tuple[List[int], bool]:
    """Greedy tokens for one request served alone, and whether every logit
    it produced was finite.

    Runs the engine's own jitted prefill and decode_step (the very programs
    the server ran), with the padded prompt placed by plain indexing in row 0
    of an otherwise idle batch, so equality with the served tokens checks
    the engine's slot insertion and batching, not the compiler."""
    scfg = engine.scfg
    P, B = scfg.prefill_bucket, scfg.max_batch
    padded = np.zeros((1, P), np.int32)
    padded[0, :min(len(ids), P)] = ids[:P]
    logits, pcache = engine._prefill(engine.params, {"tokens": padded})
    cache = jax.tree.map(lambda big, row: big.at[:, 0, :P].set(row[:, 0]),
                         engine.model.init_cache(B, scfg.max_len), pcache)
    row = np.asarray(logits)[0]
    finite = bool(np.isfinite(row).all())
    toks = [int(np.argmax(row))]
    tok_in = np.zeros((B, 1), np.int32)
    pos_in = np.zeros((B,), np.int32)
    pos_in[0] = P
    while len(toks) < n_new:
        tok_in[0, 0] = toks[-1]
        logits, cache = engine._decode(engine.params, cache,
                                       jnp.asarray(tok_in),
                                       jnp.asarray(pos_in))
        row = np.asarray(logits)[0]
        finite &= bool(np.isfinite(row).all())
        toks.append(int(np.argmax(row)))
        pos_in[0] += 1
    return toks, finite


def check_report(rep: ServeReport, expected: Sequence[List[int]],
                 n_new: int, device: jax.Device) -> None:
    """Raise :class:`SmokeFailure` unless every check on ``rep`` holds."""
    where = f"[{rep.backend}]"
    if rep.driver_error is not None:
        raise SmokeFailure(f"{where} engine driver failed: "
                           f"{rep.driver_error!r}") from rep.driver_error
    if rep.failed:
        raise SmokeFailure(f"{where} {rep.failed} of {rep.sent} requests "
                           f"failed: {rep.errors[:1]!r}")
    for i, (got, want) in enumerate(zip(rep.tokens, expected)):
        if len(got) != n_new:
            raise SmokeFailure(f"{where} request {i}: {len(got)} tokens, "
                               f"expected {n_new}")
        if got != want:
            raise SmokeFailure(f"{where} request {i}: served {got} != "
                               f"alone {want}")
    for name, tree in (("params", rep.engine.params),
                       ("cache", rep.engine.cache)):
        for leaf in jax.tree.leaves(tree):
            if leaf.devices() != {device}:
                raise SmokeFailure(f"{where} a {name} leaf is on "
                                   f"{leaf.devices()}, not {device}")


def run_smoke(model: Model, params, scfg: ServeConfig) -> List[ServeReport]:
    """Serve the same ``N_REQUESTS`` seeded prompts on each of ``BACKENDS``
    and check every answer against the request served alone; raise
    :class:`SmokeFailure` on the first check that fails."""
    device = jax.devices()[0]
    texts = make_prompts(N_REQUESTS, scfg.prefill_bucket)
    reports: List[ServeReport] = []
    expected: List[List[int]] = []
    for backend in BACKENDS:
        rep = serve(model, params, scfg, backend, texts)
        if not expected:
            for text in texts:
                toks, finite = greedy_reference(
                    rep.engine, tokenize(text, model.cfg.vocab_size),
                    scfg.max_new_tokens)
                if not finite:
                    raise SmokeFailure(f"non-finite logits for {text!r}")
                expected.append(toks)
        check_report(rep, expected, scfg.max_new_tokens, device)
        reports.append(rep)
    return reports


def main() -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform} "
              f"({device.device_kind})", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    compiles = watch_compiles()
    n_devices = len(jax.devices())
    print(f"device: platform={device.platform} kind={device.device_kind} "
          f"count={n_devices}")
    print(f"compile cache: {cache_dir}")

    t0 = time.perf_counter()
    model = Model(get_config(ARCH).with_(remat=False))
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(0)))
    print(f"setup: {ARCH} params={model.count_params()} "
          f"init_s={time.perf_counter() - t0:.1f} (compile included)")

    t0 = time.perf_counter()
    reports = run_smoke(model, params, SERVE)
    for rep in reports:
        tokens = sum(len(t) for t in rep.tokens)
        print(f"served: backend={rep.backend} sent={rep.sent} "
              f"answered={rep.answered} failed={rep.failed} "
              f"tokens={tokens} warmup_s={rep.warmup_s:.1f} "
              f"(compile included, set-up)")
    print(f"checks: passed for {len(reports)} backends "
          f"({time.perf_counter() - t0:.1f} s with the reference loops); "
          f"distinct answers {len({tuple(t) for t in reports[0].tokens})} "
          f"of {reports[0].sent}")
    print(f"compile: seconds={compiles['compile_s']:.1f} (set-up) "
          f"cache hits={compiles['hits']} misses={compiles['misses']}")
    stats = device.memory_stats() or {}
    print(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": n_devices}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
