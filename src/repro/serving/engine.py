"""Continuous-batching inference engine.

vLLM-style slot-based continuous batching, with the *orchestration* —
admission, step loop, per-request completion — running on the repro.core
async runtime.  Every pending request is a parked **fiber** (or a blocked
kernel thread under the paper's baseline backend); device work goes through
``Offload`` so the scheduler never blocks on XLA.

The engine supports the decoder-LM families (dense / moe / vlm-text); the
recurrent families serve through the same Model API but keep O(1) state, so
slot caches are trivially small.
"""
from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)
        self._insert = jax.jit(self._insert_impl)

    # ------------------------------------------------------------ plumbing
    @staticmethod
    def _insert_impl(cache: Any, pcache: Any, slot: jax.Array) -> Any:
        """Copy a prefill cache (batch=1) into one slot of the engine cache.

        Leaves are (L, B, ...) with the prefill leaf (L, 1, ...); when the
        prefill leaf is shorter along the sequence dim (full caches), it is
        placed at positions [0, P).  Recurrent-state leaves match exactly.
        """
        def ins(big, small):
            row = small[:, 0].astype(big.dtype)        # (L, ...)
            if row.shape != big.shape[:1] + big.shape[2:]:
                row = jax.lax.dynamic_update_slice_in_dim(
                    big[:, slot], row, 0, axis=1)
            return jax.lax.dynamic_update_index_in_dim(big, row, slot, axis=1)
        return jax.tree.map(ins, cache, pcache)

    def submit(self, prompt: np.ndarray,
               max_new: Optional[int] = None) -> Future:
        req = _Request(prompt=np.asarray(prompt, np.int32), done=Future(),
                       max_new=max_new or self.scfg.max_new_tokens)
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
        return (req,)

    def do_prefill(self, req: _Request) -> None:
        """Blocking device work — runs on the offload pool."""
        P = self.scfg.prefill_bucket
        n = min(len(req.prompt), P)
        padded = np.zeros((1, P), np.int32)
        padded[0, :n] = req.prompt[:n]
        logits, pcache = self._prefill(self.params, {"tokens": padded})
        self.cache = self._insert(self.cache, pcache,
                                  jnp.asarray(req.slot, jnp.int32))
        tok = int(np.argmax(np.asarray(logits)[0]))
        with self._lock:                 # the slot joins the next decode step
            req.pos = P                  # next insert position
            req.tokens.append(tok)

    def do_decode_step(self) -> List[_Request]:
        """One continuous-batching decode step (offload-pool work).
        Returns requests that finished this step."""
        with self._lock:                 # slots whose prefill has finished
            active = {s: r for s, r in self._active.items() if r.tokens}
        if not active:
            return []
        B = self.scfg.max_batch
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        for slot, req in active.items():
            toks[slot, 0] = req.tokens[-1]
            pos[slot] = req.pos
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks),
                                          jnp.asarray(pos))
        logits = np.asarray(logits)
        self.steps += 1
        finished = []
        with self._lock:
            for slot, req in active.items():
                tok = int(np.argmax(logits[slot]))
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
        return finished

    def has_work(self) -> bool:
        with self._lock:
            return bool(self._pending or self._active)
