"""The program's own spans, read for the benchmark.

The served program records its spans when a ``repro.serving.spans.
SpanRecorder`` is installed: in memory, on ``time.perf_counter()``, and
mirrored into the profiler trace under the same names.  This module reads
both:

* ``load_spans`` takes the mirrored spans from a trace's host plane, and
  ``name_idle`` names each stretch of the traced window by the innermost
  span of the engine's path open in it, so ``devtrace.breakdown`` names
  each idle gap of the device by what the engine's host side was doing:
  ``engine.offload`` (the hand-off to the offload pool),
  ``engine.idle_sleep``, ``engine.prefill`` or ``engine.decode_step`` and
  their children.  A ``host.gc`` open there wins, since a collection stops
  every Python thread.  ``idle_split_s`` splits each gap among the spans
  over it instead of naming it whole;
* the readers below take the recorder's records (``repro.serving.spans.
  Span``) and a window ``[t0, t1)`` on the same clock, and return a number
  or None (nothing to read).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from .devtrace import ENGINE_IDLE, Event, Interval, Trace, idle_gaps
from .stats import mean, percentile

GC_SPAN = "host.gc"
ENGINE_SPANS = ("engine.offload", "engine.idle_sleep", "engine.prefill",
                "engine.decode_step", "engine.lock_wait")
CHILD_PREFIXES = ("prefill.", "decode.")
REQUEST_SPANS = ("api.generate", "api.tokenize", "api.engine",
                 "api.detokenize", "engine.request", "engine.queued")


def on_engine_path(name: str) -> bool:
    return name in ENGINE_SPANS or name.startswith(CHILD_PREFIXES)


def is_program_span(name: str) -> bool:
    return (on_engine_path(name) or name in REQUEST_SPANS
            or name == GC_SPAN)


# ------------------------------------------------------------ the trace
def load_spans(directory: str) -> List[Event]:
    """The program's spans on the host plane of the trace that
    ``jax.profiler`` wrote under ``directory``, sorted by start, in the
    trace's nanoseconds; empty where it wrote none."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if is_program_span(e.name)]
    return sorted(out, key=lambda ev: (ev.start, -ev.end))


def name_idle(spans: Sequence[Event], window: Interval) -> List[Event]:
    """The stretches of ``window`` that a span of the engine's path or a
    ``host.gc`` covers, each named by the innermost of them open there (the
    latest to start), a ``host.gc`` before any; sorted and not overlapping,
    as ``devtrace.host_activity`` takes them."""
    mine = sorted((s for s in spans
                   if s.name == GC_SPAN or on_engine_path(s.name)),
                  key=lambda s: s.start)
    cuts = sorted({t for s in mine for t in (s.start, s.end)
                   if window[0] < t < window[1]} | set(window))
    out: List[Event] = []
    open_: List[Event] = []
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(mine) and mine[k].start <= a:
            open_.append(mine[k])
            k += 1
        open_ = [s for s in open_ if s.end > a]
        if not open_:
            continue
        gc_open = [s for s in open_ if s.name == GC_SPAN]
        who = gc_open[-1] if gc_open else max(
            open_, key=lambda s: (s.start, -s.end))
        if out and out[-1].name == who.name and out[-1].end == a:
            out[-1].end = b
        else:
            out.append(Event(who.name, a, b))
    return out


def idle_split_s(tr: Trace, named: Sequence[Event]) -> Dict[str, float]:
    """Idle seconds of the device by the span that ``named`` (from
    ``name_idle``) puts over each instant of them, averaged over the
    traced devices; idle time under no span is ``engine loop idle``.
    ``devtrace.breakdown`` names each gap whole by its middle; this splits
    a gap among the spans that cover it."""
    out: Dict[str, float] = defaultdict(float)
    for ops in tr.ops.values():
        for g in idle_gaps(ops, tr.window):
            covered = 0.0
            for s in named:
                lo, hi = max(g[0], s.start), min(g[1], s.end)
                if hi > lo:
                    out[s.name] += (hi - lo) / 1e9
                    covered += hi - lo
            out[ENGINE_IDLE] += (g[1] - g[0] - covered) / 1e9
    n = max(1, len(tr.ops))
    return {k: v / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


# ----------------------------------------------------------- the readers
def closed(spans: Sequence, name: str) -> list:
    return [s for s in spans if s.name == name and s.end >= s.start]


def decode_steps(spans: Sequence, t0: float, t1: float) -> list:
    """The decode steps that decoded some slot and began in the window."""
    return [s for s in closed(spans, "engine.decode_step")
            if t0 <= s.start < t1 and (s.attrs or {}).get("active", 0) > 0]


def queue_wait_p95_ms(spans: Sequence, t0: float, t1: float
                      ) -> Optional[float]:
    """``engine.queue_wait_p95_ms``: 95th percentile of ``engine.queued``
    (submit to admission) over the requests admitted in the window."""
    return percentile([(s.end - s.start) * 1e3
                       for s in closed(spans, "engine.queued")
                       if t0 <= s.end < t1], 95)


def step_host_ms(spans: Sequence, t0: float, t1: float) -> Optional[float]:
    """``engine.step_host_ms``: mean, over the decode steps of the window,
    of the step's duration less its ``decode.device_wait``: the host's own
    time per step."""
    wait = {s.parent: s.end - s.start
            for s in closed(spans, "decode.device_wait")}
    m = mean([s.end - s.start - wait.get(s.id, 0.0)
              for s in decode_steps(spans, t0, t1)])
    return None if m is None else m * 1e3


def rpc_hops_p95_ms(spans: Sequence, t0: float, t1: float
                    ) -> Optional[float]:
    """``rpc.hops_p95_ms``: 95th percentile, over the requests answered in
    the window, of ``api.generate`` less the same request's
    ``engine.request``: the tokenizer, submit and detokenizer hops and
    their queues."""
    engine = {s.rid: s.end - s.start for s in closed(spans, "engine.request")}
    return percentile([(s.end - s.start - engine[s.rid]) * 1e3
                       for s in closed(spans, "api.generate")
                       if t0 <= s.end < t1 and s.rid in engine], 95)


def kv_used_share(spans: Sequence, t0: float, t1: float, max_batch: int,
                  max_len: int) -> Optional[float]:
    """``engine.kv_used_share``: mean, over the decode steps of the window,
    of the cache entries holding real tokens (``real``: prompt tokens
    really sent and tokens generated; padding not counted) over
    ``max_batch * max_len``, in percent."""
    m = mean([s.attrs["real"] for s in decode_steps(spans, t0, t1)])
    return None if m is None else 100.0 * m / (max_batch * max_len)


def hops_p95_ms(spans: Sequence, t0: float, t1: float
                ) -> Dict[str, Optional[float]]:
    """The RPC graph's hops one by one, over the requests answered in the
    window: 95th percentiles of ``api.tokenize`` and ``api.detokenize``,
    and of ``api.engine`` less ``engine.request`` (the submit hop)."""
    answered = {s.rid for s in closed(spans, "api.generate")
                if t0 <= s.end < t1}
    dur = defaultdict(dict)
    for name in ("api.tokenize", "api.engine", "api.detokenize",
                 "engine.request"):
        for s in closed(spans, name):
            if s.rid in answered:
                dur[name][s.rid] = s.end - s.start
    submit = [v - dur["engine.request"][r]
              for r, v in dur["api.engine"].items()
              if r in dur["engine.request"]]
    return {"api.tokenize": percentile(
                [v * 1e3 for v in dur["api.tokenize"].values()], 95),
            "submit": percentile([v * 1e3 for v in submit], 95),
            "api.detokenize": percentile(
                [v * 1e3 for v in dur["api.detokenize"].values()], 95)}


def first_token_p95_ms(spans: Sequence, t0: float, t1: float
                       ) -> Optional[float]:
    """95th percentile, over the first tokens made in the window, of the
    time from the request's ``api.generate`` to its
    ``engine.first_token``: the time to first token inside the server."""
    begun = {s.rid: s.start for s in spans if s.name == "api.generate"}
    return percentile([(s.start - begun[s.rid]) * 1e3 for s in spans
                       if s.name == "engine.first_token"
                       and t0 <= s.start < t1 and s.rid in begun], 95)


def step_means(spans: Sequence, t0: float, t1: float
               ) -> Dict[str, Optional[float]]:
    """Means over the window's decode steps of the slots decoded
    (``active``), their context (``ctx``, as ``Probe`` counts it) and the
    requests queued behind them (``pending``)."""
    steps = decode_steps(spans, t0, t1)
    return {k: mean([s.attrs[k] for s in steps])
            for k in ("active", "ctx", "pending")}


def step_split_s(spans: Sequence, t0: float, t1: float) -> Dict[str, float]:
    """Seconds of the window's decode steps by child span, and the steps'
    time that no child covers (``engine.decode_step (self)``)."""
    steps = {s.id: s for s in decode_steps(spans, t0, t1)}
    out: Dict[str, float] = defaultdict(float)
    for s in spans:
        if s.parent in steps and s.end > s.start:
            out[s.name] += s.end - s.start
    covered = sum(out.values())
    out["engine.decode_step (self)"] = sum(
        s.end - s.start for s in steps.values()) - covered
    return dict(out)
