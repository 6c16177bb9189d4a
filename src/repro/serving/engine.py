"""Continuous-batching inference engine.

vLLM-style slot-based continuous batching, with the *orchestration* —
admission, step loop, per-request completion — running on the repro.core
async runtime.  Every pending request is a parked **fiber** (or a blocked
kernel thread under the paper's baseline backend); device work goes through
``Offload`` so the scheduler never blocks on XLA.

The engine supports the decoder-LM families (dense / moe / vlm-text); the
recurrent families serve through the same Model API but keep O(1) state, so
slot caches are trivially small.

Spans (``repro.core.instrument``; recorded by ``spans.SpanRecorder``): per
request ``engine.request`` (submit to done) with its child
``engine.queued`` (submit to admission) and the instant
``engine.first_token``; per prefill ``engine.prefill`` with
``prefill.dispatch`` (prefill, insert and pick dispatched),
``prefill.fetch`` (waits for them and copies one int32 id) and
``prefill.sample`` (reads it); per decode step ``engine.decode_step`` with
``decode.prepare``, ``decode.dispatch`` (the decode program and the pick
dispatched), ``decode.device_wait`` (waits for both), ``decode.fetch``
(copies B int32 ids), ``decode.sample`` (reads the id of each active slot)
and ``decode.commit``, and ``engine.lock_wait`` for each wait on the lock.

The engine's cache is donated to the programs that update it, the insert
and the decode step (``_decode_step``, which runs whatever ``_decode`` is),
so each writes its new entries in place and no step holds a second cache.

Greedy tokens are picked on the device by ``_greedy_pick``, a program of
its own applied to whatever ``_prefill``/``_decode`` return, so the host
copies one id per slot and not a row of ``vocab`` logits.  For an expert
model the pick also appends the step's two expert counts from the cache's
``moe_stats``, so they come back in the same copy; ``engine.prefill`` and
``engine.decode_step`` carry them as ``moe_local`` (assignments to the
experts held here, summed over expert layers and over every row the step
computed) and ``moe_max_load`` (the largest held expert's load, summed
over expert layers).
"""
from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import instrument
from ..core.context import trace_ticket
from ..core.future import Future
from ..models import Model


@dataclass
class ServeConfig:
    max_batch: int = 4            # concurrent decode slots
    max_len: int = 256            # slot KV capacity
    prefill_bucket: int = 64      # prompts padded to this length
    max_new_tokens: int = 32
    eos_token: int = -1           # -1: never stops early
    greedy: bool = True


@dataclass
class _Request:
    prompt: np.ndarray
    done: Future
    max_new: int
    tokens: List[int] = field(default_factory=list)
    slot: int = -1
    pos: int = 0                  # next absolute position to write
    rid: Optional[int] = None     # trace id of its spans (traced runs)


def _greedy_pick(logits: jax.Array,
                 moe_stats: Optional[jax.Array] = None) -> jax.Array:
    """Each row's largest logit's id, the lowest on ties, as ``np.argmax``;
    with ``moe_stats`` (1, B, 2) then its rows' held assignments summed and
    the largest held load: (B + 2,) int32."""
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if moe_stats is None:
        return ids
    return jnp.concatenate([ids, moe_stats[0, :, 0].sum(keepdims=True),
                            moe_stats[0, :1, 1]])


def _moe_stats(cache: Any) -> Tuple[jax.Array, ...]:
    """The arguments that make ``_greedy_pick`` append the expert counts."""
    return (cache["moe_stats"],) if "moe_stats" in cache else ()


def _moe_attrs(ids: np.ndarray, n: int) -> dict:
    """The expert counts that follow the ``n`` ids, if the pick added them."""
    if len(ids) == n:
        return {}
    return {"moe_local": int(ids[n]), "moe_max_load": int(ids[n + 1])}


def _end_request(h: instrument.Hooks, rid: int, _done: Future) -> None:
    h.span_end("engine.queued", rid, None)    # open if it failed unadmitted
    h.span_end("engine.request", rid, None)


class InferenceEngine:
    """Slot-based continuous batching over a shared padded KV cache."""

    def __init__(self, model: Model, params: Any, scfg: ServeConfig) -> None:
        assert not model.cfg.is_encdec, \
            "the engine serves decoder-only families (dense/moe/ssm/hybrid)"
        self.model = model
        self.params = params
        self.scfg = scfg
        cfg = model.cfg

        self._lock = threading.Lock()
        self._pending: Deque[_Request] = deque()
        self._active: Dict[int, _Request] = {}   # slot -> request holding it
        self._free = list(range(scfg.max_batch))
        self._error: Optional[BaseException] = None  # set once the driver died
        # engine-wide decode state (padded to max_batch)
        self.cache = model.init_cache(scfg.max_batch, scfg.max_len)
        self.steps = 0
        self.generated = 0

        # --- jitted device functions -------------------------------------
        # ``_decode`` is the step as a plain function of its arguments; the
        # served program, ``_decode_step``, calls whatever ``_decode`` is
        # when it is first traced, on a donated cache, so a wrapper set on
        # ``_decode`` after construction is what is served
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)
        self._step = jax.jit(self._decode_step, donate_argnums=1)
        self._insert = jax.jit(self._insert_impl, donate_argnums=0)
        self._pick = jax.jit(_greedy_pick)

    # ------------------------------------------------------------ plumbing
    def _decode_step(self, params: Any, cache: Any, toks: jax.Array,
                     pos: jax.Array) -> Tuple[jax.Array, Any]:
        """The served decode step: ``_decode`` traced into the program
        that donates the engine's cache."""
        return self._decode(params, cache, toks, pos)

    @staticmethod
    def _insert_impl(cache: Any, pcache: Any, slot: jax.Array) -> Any:
        """Copy a prefill cache (batch=1) into one slot of the engine cache,
        in place where the cache is donated.

        Leaves are (L, B, ...) with the prefill leaf (L, 1, ...); when the
        prefill leaf is shorter along the sequence dim (full caches), it is
        placed at positions [0, P).  Recurrent-state leaves match exactly.
        """
        def ins(big, small):
            start = (0, slot) + (0,) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(big, small.astype(big.dtype),
                                                start)
        with jax.named_scope("cache_update"):
            return jax.tree.map(ins, cache, pcache)

    def submit(self, prompt: np.ndarray, max_new: Optional[int] = None,
               rid: Optional[int] = None) -> Future:
        """Queue a request; ``rid`` labels its spans (a fresh trace id when
        spans are recorded and none is given)."""
        req = _Request(prompt=np.asarray(prompt, np.int32), done=Future(),
                       max_new=max_new or self.scfg.max_new_tokens)
        h = instrument.hooks
        if h is not None:
            req.rid = trace_ticket() if rid is None else rid
            h.span_begin("engine.request", req.rid)
            h.span_begin("engine.queued", req.rid)
            req.done.add_done_callback(partial(_end_request, h, req.rid))
        with self._lock:
            error = self._error
            if error is None:
                self._pending.append(req)
        if error is not None:
            req.done.set_exception(error)
        return req.done

    def fail_all(self, exc: BaseException) -> None:
        """The driver died with ``exc``: fail every pending and admitted
        request with it, and every request submitted from now on."""
        with self._lock:
            self._error = exc
            reqs = list(self._pending) + list(self._active.values())
            self._pending.clear()
            self._active.clear()
        for req in reqs:
            req.done.set_exception(exc)

    # ------------------------------------------------------- engine phases
    def admit_one(self) -> Optional[Tuple[Any, ...]]:
        """Pop one pending request + a free slot (engine fiber calls this)."""
        with self._lock:
            if not self._pending or not self._free:
                return None
            req = self._pending.popleft()
            req.slot = self._free.pop()
            self._active[req.slot] = req
        h = instrument.hooks
        if h is not None:
            h.span_end("engine.queued", req.rid, None)
        return (req,)

    def do_prefill(self, req: _Request) -> None:
        """Blocking device work — runs on the offload pool."""
        h = instrument.hooks
        if h is not None:
            h.span_begin("engine.prefill", None)
            h.span_begin("prefill.dispatch", None)
        P = self.scfg.prefill_bucket
        n = min(len(req.prompt), P)
        padded = np.zeros((1, P), np.int32)
        padded[0, :n] = req.prompt[:n]
        logits, pcache = self._prefill(self.params, {"tokens": padded})
        self.cache = self._insert(self.cache, pcache,
                                  jnp.asarray(req.slot, jnp.int32))
        ids = self._pick(logits, *_moe_stats(pcache))
        if h is not None:
            h.span_end("prefill.dispatch", None, None)
            h.span_begin("prefill.fetch", None)
        ids = np.asarray(ids)
        if h is not None:
            h.span_end("prefill.fetch", None, None)
            h.span_begin("prefill.sample", None)
        tok = int(ids[0])
        if h is not None:
            h.span_end("prefill.sample", None, None)
        with self._lock:                 # the slot joins the next decode step
            req.pos = P                  # next insert position
            req.tokens.append(tok)
        if h is not None:
            h.event("engine.first_token", req.rid, None)
            h.span_end("engine.prefill", None, _moe_attrs(ids, 1) or None)

    def do_decode_step(self) -> List[_Request]:
        """One continuous-batching decode step (offload-pool work).
        Returns requests that finished this step."""
        h = instrument.hooks
        if h is not None:
            h.span_begin("engine.decode_step", None)
            h.span_begin("decode.prepare", None)
            h.span_begin("engine.lock_wait", None)
        with self._lock:                 # slots whose prefill has finished
            if h is not None:
                h.span_end("engine.lock_wait", None, None)
            active = {s: r for s, r in self._active.items() if r.tokens}
            pending = len(self._pending)
        if not active:
            if h is not None:
                h.span_end("decode.prepare", None, None)
                h.span_end("engine.decode_step", None, {"active": 0})
            return []
        B = self.scfg.max_batch
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        for slot, req in active.items():
            toks[slot, 0] = req.tokens[-1]
            pos[slot] = req.pos
        if h is not None:
            h.span_end("decode.prepare", None, None)
            h.span_begin("decode.dispatch", None)
        logits, self.cache = self._step(self.params, self.cache,
                                        jnp.asarray(toks), jnp.asarray(pos))
        ids = self._pick(logits, *_moe_stats(self.cache))
        if h is not None:
            h.span_end("decode.dispatch", None, None)
            h.span_begin("decode.device_wait", None)
        ids.block_until_ready()
        if h is not None:
            h.span_end("decode.device_wait", None, None)
            h.span_begin("decode.fetch", None)
        ids = np.asarray(ids)
        if h is not None:
            h.span_end("decode.fetch", None, None)
            h.span_begin("decode.sample", None)
        picks = {slot: int(ids[slot]) for slot in active}
        self.steps += 1
        if h is not None:
            h.span_end("decode.sample", None, None)
            h.span_begin("decode.commit", None)
            h.span_begin("engine.lock_wait", None)
        finished = []
        with self._lock:
            if h is not None:
                h.span_end("engine.lock_wait", None, None)
            for slot, req in active.items():
                tok = picks[slot]
                req.tokens.append(tok)
                req.pos += 1
                self.generated += 1
                done = (len(req.tokens) >= req.max_new
                        or tok == self.scfg.eos_token
                        or req.pos >= self.scfg.max_len - 1)
                if done:
                    finished.append(req)
                    del self._active[req.slot]
                    self._free.append(req.slot)
        for req in finished:          # resolve outside the lock
            req.done.set_result(req.tokens)
        if h is not None:
            h.span_end("decode.commit", None, None)
            h.span_end("engine.decode_step", None,
                       dict(self._step_attrs(active, pending),
                            **_moe_attrs(ids, B)))
        return finished

    def _step_attrs(self, active: Dict[int, _Request],
                    pending: int) -> dict:
        """The numbers of the step just taken: its index, the slots it
        decoded, their context (cache entries attended, padding included),
        the entries that hold real tokens (the prompt really sent, and the
        tokens generated), and the requests still queued."""
        P = self.scfg.prefill_bucket
        ctx = sum(r.pos for r in active.values())   # pos already advanced
        real = sum(min(len(r.prompt), P) + len(r.tokens) - 1
                   for r in active.values())
        return {"step": self.steps, "active": len(active), "ctx": ctx,
                "real": real, "pending": pending}

    def has_work(self) -> bool:
        with self._lock:
            return bool(self._pending or self._active)
