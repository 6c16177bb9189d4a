"""In-memory recorder of the LLM server's spans and events.

:class:`SpanRecorder` subscribes to ``repro.core.instrument``'s span
events: ``span_begin`` / ``span_end`` / ``event``, emitted by the RPC graph
(``service.py``) and the engine (``engine.py``) under the seam's one-branch
idiom, so with no recorder installed a span site costs one attribute load
and one ``is not None`` test.  Installed, it keeps one :class:`Span` per
span, on ``time.perf_counter()``, and mirrors each into a
``jax.profiler.TraceAnnotation`` of the same name, which puts the
program's spans on a profiler trace's clock beside the device's work.

Parents are tracked per request: each ``rid`` has its own stack of open
spans, and a span's parent is the innermost span of its ``rid`` open when
it begins.  A stack per thread would not do: a fiber resumes on whichever
carrier is free, many requests interleave on one carrier, and the engine
loop hands its work to a pool thread.  Spans with ``rid`` None are the
engine loop's, which one driver runs one at a time, and ``host.gc``, which
stops every Python thread; so the recorder assumes one engine per process.
"""
from __future__ import annotations

import gc
import itertools
import math
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from ..core import instrument

GC_SPAN = "host.gc"


@dataclass
class Span:
    """One span (``end`` NaN while open) or one event (``end == start``).
    ``parent`` is the enclosing span's ``id``, or None."""
    id: int
    name: str
    rid: Optional[int]
    parent: Optional[int]
    start: float
    end: float = math.nan
    attrs: Optional[dict] = None


class SpanRecorder(instrument.Hooks):
    """Keeps every span and event in ``spans``, in the order they began.
    ``install`` makes it the seam's hook table and adds a ``gc.callbacks``
    entry that records each collection as a ``host.gc`` span; ``uninstall``
    takes both away again."""

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.spans: List[Span] = []             # appends are atomic
        self._ids = itertools.count()
        self._lock = threading.Lock()           # guards _open
        self._open: Dict[Optional[int], List[Span]] = {}
        self._mirrors: Dict[int, Any] = {}      # span id -> its annotation
        self._gc_span: Optional[Span] = None
        self._gc_mirror: Any = None

    def install(self) -> None:
        instrument.install(self)
        gc.callbacks.append(self._on_gc)

    def uninstall(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if instrument.hooks is self:
            instrument.uninstall()

    # ------------------------------------------------------------- events
    def span_begin(self, name: str, rid: Optional[int]) -> None:
        # the clock is read next to the mirror's own reading at each end, so
        # that a collection or a switch of threads in between counts in both
        sp = Span(next(self._ids), name, rid, None, math.nan)
        mirror = self._annotation(name)
        mirror.__enter__()
        sp.start = time.perf_counter()
        self._mirrors[sp.id] = mirror           # before another thread
        self.spans.append(sp)                   # can end it
        with self._lock:
            stack = self._open.setdefault(rid, [])
            if stack:
                sp.parent = stack[-1].id
            stack.append(sp)

    def span_end(self, name: str, rid: Optional[int],
                 attrs: Optional[dict]) -> None:
        with self._lock:
            stack = self._open.get(rid, ())
            k = next((k for k in range(len(stack) - 1, -1, -1)
                      if stack[k].name == name), None)
            if k is None:                       # begun before install
                return
            sp = stack.pop(k)
        self._mirrors.pop(sp.id).__exit__(None, None, None)
        sp.end, sp.attrs = time.perf_counter(), attrs

    def event(self, name: str, rid: Optional[int],
              attrs: Optional[dict]) -> None:
        t = time.perf_counter()
        with self._lock:
            stack = self._open.get(rid)
            parent = stack[-1].id if stack else None
        self.spans.append(Span(next(self._ids), name, rid, parent, t, t,
                               attrs))

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        # A collection runs on whichever thread allocated, possibly inside
        # span_begin's critical section: it takes no lock and no parent.
        if phase == "start":
            sp = Span(next(self._ids), GC_SPAN, None, None, math.nan)
            self._gc_mirror = self._annotation(GC_SPAN)
            self._gc_mirror.__enter__()
            sp.start = time.perf_counter()
            self._gc_span = sp
            return
        sp, self._gc_span = self._gc_span, None
        if sp is None:                          # began before install
            return
        self._gc_mirror.__exit__(None, None, None)
        sp.end = time.perf_counter()
        sp.attrs = {"generation": info["generation"]}
        self.spans.append(sp)
