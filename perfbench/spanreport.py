"""One run of one cell with the program's span recorder installed; see
``spanrun.py`` for the command and what it prints."""
from __future__ import annotations

import json
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

from . import devtrace, harness
from . import spans as sp
from .stats import mean

WARMUP_WAIT_S = 1800.0      # set-up, compiles included, must end by then


def trace_middle(rec: Any, seconds: float, out: List[harness.Tracer]) -> None:
    """Trace the middle of the window as ``run.py --trace 1`` does, timed
    from the end of the warm-up request's ``api.generate`` span (the
    window opens 50 ms after it).  Runs in its own thread."""
    deadline = time.perf_counter() + WARMUP_WAIT_S
    while time.perf_counter() < deadline:
        ends = [s.end for s in rec.spans
                if s.name == "api.generate" and s.end >= s.start]
        if ends:
            t0 = ends[0] + 0.05
            tracer = harness.Tracer(
                t0 + max(0.0, (seconds - harness.TRACE_S) / 2),
                min(harness.TRACE_S, seconds))
            out.append(tracer)
            tracer.run()
            return
        time.sleep(0.005)


def longest_steps(spans: Sequence, t0: float, t1: float, n: int = 3
                  ) -> List[Dict[str, float]]:
    """The ``n`` longest decode steps of the window: when each began (s
    after ``t0``), its duration and its children's, in ms, and the
    collections that overlap it."""
    steps = sorted(sp.decode_steps(spans, t0, t1),
                   key=lambda s: s.start - s.end)[:n]
    out = []
    for st in steps:
        row = {"step": st.attrs["step"], "at_s": st.start - t0,
               "engine.decode_step": (st.end - st.start) * 1e3}
        for s in spans:
            if s.parent == st.id and s.end > s.start:
                row[s.name] = row.get(s.name, 0.0) + (s.end - s.start) * 1e3
        row["host.gc"] = sum((min(s.end, st.end) - max(s.start, st.start))
                             * 1e3 for s in sp.closed(spans, sp.GC_SPAN)
                             if s.start < st.end and s.end > st.start)
        out.append(row)
    return out


def collections(spans: Sequence, t0: float, t1: float) -> Dict[str, float]:
    gcs = [s for s in sp.closed(spans, sp.GC_SPAN) if t0 <= s.start < t1]
    return {"count": len(gcs),
            "total_s": sum(s.end - s.start for s in gcs),
            "longest_ms": max(((s.end - s.start) * 1e3 for s in gcs),
                              default=0.0),
            "gen2": sum(s.attrs["generation"] == 2 for s in gcs)}


def report(run: harness.Run, spans: Sequence,
           tracer: Optional[harness.Tracer]) -> dict:
    t0, t1 = run.t0, run.t1
    serve = run.cell.serve
    steps = sp.decode_steps(spans, t0, t1)
    out: Dict[str, Any] = {
        "tokens_per_s": run.tokens_in_window / run.seconds,
        "failed": sum(not run.requests[i].ok for i in run.attempted),
        "metrics": {
            "engine.queue_wait_p95_ms": sp.queue_wait_p95_ms(spans, t0, t1),
            "engine.step_host_ms": sp.step_host_ms(spans, t0, t1),
            "rpc.hops_p95_ms": sp.rpc_hops_p95_ms(spans, t0, t1),
            "engine.kv_used_share": sp.kv_used_share(
                spans, t0, t1, serve["max_batch"], serve["max_len"])},
        "hops_p95_ms": sp.hops_p95_ms(spans, t0, t1),
        "first_token_p95_ms": sp.first_token_p95_ms(spans, t0, t1),
        "decode_steps": len(steps),
        "decode_step_ms": mean([(s.end - s.start) * 1e3 for s in steps]),
        "step_means": sp.step_means(spans, t0, t1),
        "decode_split_s": sp.step_split_s(spans, t0, t1),
        "longest_steps": longest_steps(spans, t0, t1),
        "gc": collections(spans, t0, t1),
    }
    if tracer is None:
        return out
    tr = devtrace.load(tracer.dir)
    if tr is None:
        out["trace"] = None
        return out
    h0, h1 = tracer.host
    traced = sp.decode_steps(spans, h0, h1)
    dec = [e.end - e.start for mods in tr.modules.values()
           for e in devtrace.module_events(mods, tr.window, "decode_step")]
    out["trace"] = {
        "idle_share": 100.0 * (1.0 - devtrace.device_busy_s(tr)
                               / ((tr.window[1] - tr.window[0]) / 1e9)),
        "step.decode_ms": None if not dec else mean(dec) / 1e6,
        "engine.step_host_ms": sp.step_host_ms(spans, h0, h1),
        "decode_step_ms": mean([(s.end - s.start) * 1e3 for s in traced]),
        "decode_split_s": sp.step_split_s(spans, h0, h1),
    }
    tr.spans = sp.name_idle(sp.load_spans(tracer.dir), tr.window)
    out["trace"]["idle_split_s"] = sp.idle_split_s(tr, tr.spans)
    out["trace"]["longest_steps"] = longest_steps(spans, h0, h1)
    out["breakdown"] = devtrace.breakdown(tr, top=24)
    return out


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None,
         allow_cpu: bool = False, root: Path = harness.ROOT,
         base: Path = harness.HERE) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = execute(args.workload, args.seed, args.seconds,
                     bool(args.profile), t_start, allow_cpu, root, base)
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


def execute(workload: str, seed: int, seconds: float, profile: bool,
            t_start: float, allow_cpu: bool = False, root: Path = harness.ROOT,
            base: Path = harness.HERE) -> Optional[dict]:
    """The run and its report, or None where the run cannot measure."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache, watch_compiles
    from repro.serving.spans import SpanRecorder

    try:
        cell = harness.load_cell(workload, root, base)
        peaks_all = json.loads((base / "peaks.json").read_text())["devices"]
        device = harness.device_info(jax, cell.chips, peaks_all, allow_cpu)
    except harness.BenchError as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return None
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"

    def log(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    log(f"spans: cell {workload} seed {seed} seconds {seconds} "
        f"profile {int(profile)}")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = watch_compiles()
    rec = SpanRecorder()
    tracers: List[harness.Tracer] = []
    watcher = None
    if profile:
        watcher = threading.Thread(target=trace_middle,
                                   args=(rec, seconds, tracers), daemon=True)
    rec.install()
    try:
        if watcher is not None:
            watcher.start()
        run = harness.serve_window(cell, seed, seconds, False, t_start,
                                   peaks_all.get(device["kind"], {}), log,
                                   compiles)
        if watcher is not None:
            watcher.join(timeout=harness.STOP_S)
    finally:
        rec.uninstall()
    tracer = tracers[0] if tracers else None
    if tracer is not None and tracer.error is not None:
        raise tracer.error
    try:
        result = report(run, rec.spans, tracer)
    finally:
        if tracer is not None:
            shutil.rmtree(tracer.dir, ignore_errors=True)
    result["device"] = device
    return result
