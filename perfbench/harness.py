"""One run of one cell: set-up, a measured window, the check, the result.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration, a traffic mix
and a cell file, each found by name under this directory:

* ``configs/<config>.json``: the published sizes, the ``program`` arguments
  of ``repro.models.ModelConfig`` that run them, and the ``reference``
  module (``references/<reference>.py``).  That module knows the model's
  kind: ``layer_shapes(c)``, ``block(x, w, c, quant)``, ``counts(c)`` (the
  FLOPs and bytes the roofline readers use) and optionally
  ``layer_weights(seed, c, layer)`` where the layers come in groups beyond
  ``blocks`` (see ``references/__init__.py``);
* ``traffic/<traffic>.json``: the mix (see ``traffic.py``);
* ``workloads/<cell>.json``: the ``ServeConfig``, the RPC backend, how many
  finished requests the check samples, and the check's limits;
* ``metrics/<metric>.py``: one reader per metric, ``read(run)`` returning a
  number or None (nothing to read in this run).  A metric entry without a
  ``workloads`` list applies to every cell.

A configuration of a new kind is therefore new files alone: one
configuration file, one reference module, its cells and its metric
readers.

Set-up makes the weights from the seed, builds ``build_llm_app`` and sends
one warm-up request, which compiles the prefill at the bucket, the insert
and the decode step at ``max_batch``.  The window then drives the app through
``app.send("api", "generate", {"text", "max_new"})``: an open loop sends
each request when it is due, a closed loop keeps ``clients`` requests in
flight.  ``--trace 1`` adds the harness's spans around the engine's phases
and a profiler trace of the middle ``TRACE_S`` seconds; ``--trace 0`` adds
only a counter of prefills (no clock), for the first tokens.

After the window the app is stopped, the device's peak memory read, every
array freed, and the configuration's float32 reference run over a sample
of the finished requests (``reference.py``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import queue
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import reference, traffic, weights
from .flops import Counts, counts
from .stats import percentile

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
DRAIN_S = 60.0        # how long after the window an answer due in it may come
TRACE_S = 5.0         # traced seconds, in the middle of the window
STOP_S = 60.0
WARMUP_TEXT = "warm-up"
MISS_MS = 1e9         # a tail that a miss (+inf) decides is reported as this
NO_READING = 1e9      # the gap when no served token could be compared


class BenchError(RuntimeError):
    """The run cannot measure: no accelerator, too few chips, an unknown
    device kind or cell.  No result is printed."""


# --------------------------------------------------------------- the cell
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    serve: dict
    backend: str
    sample_requests: int
    limits: Dict[str, float]
    metrics: Dict[str, List[dict]]        # "end_to_end" / "per_layer" -> entries

    @property
    def answer_max(self) -> int:
        return int(self.traffic["output_tokens"]["max"])


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT, base: Path = HERE) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")

    def data(kind: str, key: str) -> dict:
        return json.loads((base / kind / f"{key}.json").read_text())

    cellf = data("workloads", name)
    return Cell(name=name, chips=int(entry["chips"]),
                config=data("configs", entry["config"]),
                traffic=data("traffic", entry["traffic"]),
                serve=cellf["serve"], backend=cellf["backend"],
                sample_requests=int(cellf["sample_requests"]),
                limits={k: float(v) for k, v in cellf["limits"].items()},
                metrics={kind: [m for m in bench[kind] if _applies(m, name)]
                         for kind in ("end_to_end", "per_layer")})


def reader(base: Path, metric: str) -> Callable[["Run"], Optional[float]]:
    path = base / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ the records
@dataclass
class Sent:
    due: float                  # perf_counter seconds
    sent: float = math.nan
    reply: float = math.nan
    ok: Optional[bool] = None   # None: no answer yet
    tokens: Optional[List[int]] = None
    text: Optional[str] = None


@dataclass
class Run:
    """What the metric readers read.  Times are ``time.perf_counter()``
    seconds unless named ``_ns`` (the trace's clock)."""
    cell: Cell
    seconds: float
    t0: float                               # window opens (first request due)
    t1: float                               # window closes
    setup_s: float
    loop: str
    requests: List[Sent]                    # due in the window (open) or sent
    schedule: List[traffic.Request]
    tokens_in_window: int
    counts: Counts
    peaks: dict
    prefills: List[dict] = field(default_factory=list)   # traced runs
    decodes: List[dict] = field(default_factory=list)
    engine_times: Dict[bytes, List[float]] = field(default_factory=dict)
    trace: Any = None                       # devtrace.Trace, traced runs
    trace_host: tuple = (math.nan, math.nan)  # the traced window, perf_counter

    def in_trace(self, t: float) -> bool:
        return self.trace_host[0] <= t < self.trace_host[1]

    @property
    def attempted(self) -> List[int]:
        """Indices of the requests the metrics judge: due in the window
        (open loop), or answered in it (closed loop)."""
        if self.loop == "open":
            return [i for i, r in enumerate(self.requests)
                    if r.due < self.t1]
        return [i for i, r in enumerate(self.requests)
                if r.ok is not None and r.reply <= self.t1]


# ---------------------------------------------------------------- probes
class Probe:
    """Wraps the engine's phases on the instance.  Untraced: a prefill
    counter and nothing else.  Traced: host spans (``TraceAnnotation``,
    visible in the profiler trace) and the records the per-layer metrics
    read."""

    def __init__(self, engine: Any, traced: bool) -> None:
        import jax

        self.n_prefills = 0
        self.prefills: List[dict] = []
        self.decodes: List[dict] = []
        self.engine_times: Dict[bytes, List[float]] = {}
        prefill, decode, submit = (engine.do_prefill, engine.do_decode_step,
                                   engine.submit)
        ann = jax.profiler.TraceAnnotation
        clock = time.perf_counter

        if not traced:
            def do_prefill(req: Any) -> None:
                prefill(req)
                self.n_prefills += 1
            engine.do_prefill = do_prefill
            return

        def traced_prefill(req: Any) -> None:
            t = clock()
            with ann("do_prefill"):
                prefill(req)
            self.prefills.append({"start": t, "end": clock(),
                                  "key": req.prompt.tobytes(),
                                  "prompt": len(req.prompt)})
            self.n_prefills += 1

        def traced_decode() -> Any:
            with engine._lock:
                pos = [r.pos for r in engine._active.values() if r.tokens]
            t = clock()
            with ann("do_decode_step"):
                finished = decode()
            if pos:
                self.decodes.append({"start": t, "end": clock(),
                                     "active": len(pos),
                                     "ctx": sum(p + 1 for p in pos)})
            return finished

        def traced_submit(prompt: Any, max_new: Any = None) -> Any:
            key = np.asarray(prompt, np.int32).tobytes()
            times = self.engine_times.setdefault(key, [clock(), math.nan])
            fut = submit(prompt, max_new)
            fut.add_done_callback(lambda _f: times.__setitem__(1, clock()))
            return fut

        engine.do_prefill = traced_prefill
        engine.do_decode_step = traced_decode
        engine.submit = traced_submit


class Tracer(threading.Thread):
    """Traces ``TRACE_S`` seconds from ``start`` (perf_counter) into a
    fresh directory under ``TMPDIR``."""

    def __init__(self, start: float, seconds: float) -> None:
        super().__init__(daemon=True)
        self.start_at, self.seconds = start, seconds
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self.error: Optional[BaseException] = None
        self.host = (math.nan, math.nan)   # perf_counter at the span's ends

    def run(self) -> None:
        import jax
        try:
            time.sleep(max(0.0, self.start_at - time.perf_counter()))
            jax.profiler.start_trace(self.dir)
            try:
                with jax.profiler.TraceAnnotation("perfbench_window"):
                    t = time.perf_counter()
                    time.sleep(self.seconds)
                    self.host = (t, time.perf_counter())
            finally:
                jax.profiler.stop_trace()
        except BaseException as exc:       # reported by the caller
            self.error = exc


# ----------------------------------------------------------------- client
def _send(app: Any, r: Sent, req: traffic.Request,
          done: Optional[Callable[[], None]] = None) -> None:
    def on_done(fut: Any) -> None:
        r.reply = time.perf_counter()
        if fut.exception() is None:
            out = fut.result()
            r.tokens, r.text = [int(t) for t in out["tokens"]], out["text"]
            r.ok = True
        else:
            r.ok = False
        if done is not None:
            done()

    r.sent = time.perf_counter()
    app.send("api", "generate", {"text": req.text, "max_new": req.max_new}
             ).add_done_callback(on_done)


def open_loop(app: Any, sched: List[traffic.Request], t0: float,
              seconds: float) -> List[Sent]:
    recs = [Sent(due=t0 + q.due_s) for q in sched]
    for r, q in zip(recs, sched):
        wait = r.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _send(app, r, q)
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    return recs


def closed_loop(app: Any, sched: List[traffic.Request], clients: int,
                t0: float, seconds: float) -> List[Sent]:
    ready: "queue.Queue[int]" = queue.Queue()
    recs: List[Sent] = []

    def send_next() -> None:
        i = len(recs)
        q = sched[i % len(sched)]
        recs.append(Sent(due=time.perf_counter()))
        _send(app, recs[i], q, partial(ready.put, i))

    for _ in range(clients):
        send_next()
    while True:
        left = t0 + seconds - time.perf_counter()
        if left <= 0:
            break
        try:
            ready.get(timeout=left)
        except queue.Empty:
            break
        send_next()
    return recs


def drain(recs: List[Sent], deadline: float) -> None:
    """Wait until every request has an answer, or ``deadline``."""
    while any(r.ok is None for r in recs) and time.perf_counter() < deadline:
        time.sleep(0.01)


# ------------------------------------------------------------------ check
def check(run: Run, cell: Cell, seed: int, controls: Tuple[str, ...] = ()
          ) -> Dict[str, Dict[str, float]]:
    """Each number the check compares, with its limit.

    * ``unanswered``: requests judged that failed or never answered;
    * ``wrong_length``: answers without exactly ``max_new`` tokens;
    * ``detok_mismatch``: answers whose text is not their tokens, detokenized;
    * ``logit_gap``: the widest gap, over a seeded sample of answers with the
      longest in it, between the reference's best logit and its logit of the
      served token (``reference.py``);
    * ``sampled_tokens``: how many served tokens the sample holds (at least 1).

    For each of ``controls`` it also returns ``control_gap.<control>``, the
    same widest gap for the tokens that control puts first, on the same
    sample."""
    judged = [run.requests[i] for i in run.attempted]
    answered = [(i, r) for i, r in zip(run.attempted, judged) if r.ok]
    out: Dict[str, Dict[str, float]] = {
        "unanswered": {"value": len(judged) - len(answered),
                       "limit": cell.limits["unanswered"]},
        "wrong_length": {"value": sum(len(r.tokens) != run.schedule[
            i % len(run.schedule)].max_new for i, r in answered),
            "limit": cell.limits["wrong_length"]},
        "detok_mismatch": {"value": sum(
            r.text != " ".join(str(t) for t in r.tokens) for _, r in answered),
            "limit": cell.limits["detok_mismatch"]},
    }
    picks = sample(answered, cell.sample_requests, seed)
    P = cell.serve["prefill_bucket"]
    fed, served = [], []
    for i, r in picks:
        text = run.schedule[i % len(run.schedule)].text
        ids = np.frombuffer(text.encode("utf-8"), np.uint8).astype(np.int32)
        ids = ids % cell.config["vocab_size"]
        padded = np.zeros(P, np.int32)      # the engine pads to its bucket
        padded[:min(len(ids), P)] = ids[:P]
        fed.append(np.concatenate([padded, np.asarray(r.tokens[:-1], np.int32)]))
        served.append(np.asarray(r.tokens, np.int32))
    out["sampled_tokens"] = {"value": sum(len(s) for s in served),
                             "limit": 1}
    if served:
        ref, ctrl = reference.gaps(cell.config, seed, fed, served, P - 1,
                                   P + cell.answer_max - 1, controls)
        gap = max(float(g.max()) for g in ref)
    else:
        ctrl, gap = {}, NO_READING
    out["logit_gap"] = {"value": gap, "limit": cell.limits["logit_gap"]}
    for q, gaps in ctrl.items():
        out[f"control_gap.{q}"] = {"value": max(float(g.max()) for g in gaps),
                                   "limit": cell.limits["logit_gap"]}
    return out


def sample(answered: List[Any], n: int, seed: int) -> List[Any]:
    """The longest answer and ``n - 1`` others drawn from the seed."""
    if not answered:
        return []
    longest = max(range(len(answered)), key=lambda j: len(answered[j][1].tokens))
    rest = [j for j in range(len(answered)) if j != longest]
    rng = np.random.default_rng([int(seed), 9])
    picked = rng.permutation(rest)[:max(0, n - 1)].tolist()
    return [answered[j] for j in [longest] + sorted(picked)]


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every number within its limit; at least one served token compared.
    A ``control_gap.*`` is the control's reading, not the program's: see
    ``as_control``."""
    ok = all(c["value"] <= c["limit"] for k, c in checks.items()
             if k != "sampled_tokens" and not k.startswith("control_gap"))
    return ok and checks["sampled_tokens"]["value"] >= 1


def as_control(checks: Dict[str, Dict[str, float]], control: str
               ) -> Dict[str, Dict[str, float]]:
    """The checks with ``control`` put in the program's place: its gap
    stands as ``logit_gap``, so ``passed`` judges the control as it judges
    a run."""
    out = {k: v for k, v in checks.items() if not k.startswith("control_gap")}
    out["logit_gap"] = dict(checks[f"control_gap.{control}"])
    return out


# -------------------------------------------------------------- the run
def device_info(jax: Any, chips: int, peaks_all: dict, allow_cpu: bool
                ) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform == "cpu" and not allow_cpu:
        raise BenchError("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if d0.device_kind not in peaks_all and not allow_cpu:
        raise BenchError(f"device kind {d0.device_kind!r} is not in peaks.json")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def serve_window(cell: Cell, seed: int, seconds: float, traced: bool,
                 t_start: float, peaks: dict, log: Callable[[str], None],
                 compiles: Any = None) -> Run:
    """Set-up, the window, the drain and the stop; returns the records.
    The program's arrays stay on the device until ``free_device``."""
    import jax
    from repro.models import Model
    from repro.models.config import ModelConfig
    from repro.serving import ServeConfig, build_llm_app

    model = Model(ModelConfig(**cell.config["program"]))
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_params(abstract, model.cfg.n_layers, seed)
    scfg = ServeConfig(**cell.serve)
    app = build_llm_app(model, params, scfg, backend=cell.backend)
    engine = app.state["engine"]
    probe = Probe(engine, traced)
    sched = traffic.schedule(cell.traffic, seed, seconds)
    app.start()
    try:
        loop = app.send("engine", "run", None)
        app.send("api", "generate", {"text": WARMUP_TEXT, "max_new": 3}
                 ).wait(timeout=1200)
        before = dict(compiles or {})
        tracer = None
        t0 = time.perf_counter() + 0.05
        if traced:
            tracer = Tracer(t0 + max(0.0, (seconds - TRACE_S) / 2),
                            min(TRACE_S, seconds))
            tracer.start()
        setup_s = t0 - t_start
        tok0, pre0 = engine.generated, probe.n_prefills
        if cell.traffic["loop"] == "open":
            recs = open_loop(app, sched, t0, seconds)
        else:
            recs = closed_loop(app, sched, int(cell.traffic["clients"]), t0,
                               seconds)
        t1 = t0 + seconds
        tokens = engine.generated - tok0 + probe.n_prefills - pre0
        after = dict(compiles or {})
        if cell.traffic["loop"] == "open":
            drain(recs, t1 + DRAIN_S)
        if tracer is not None:
            tracer.join(timeout=STOP_S)
            if tracer.error is not None:
                raise tracer.error
        log(f"window: {len(recs)} requests sent, {tokens} tokens; "
            f"compiles in window: "
            f"{after.get('misses', 0) - before.get('misses', 0)} compiled, "
            f"{after.get('hits', 0) - before.get('hits', 0)} read from the "
            f"cache, {after.get('compile_s', 0) - before.get('compile_s', 0):.3f}"
            f" s compiling")
    finally:
        app.services["engine"].state["stop"] = True
        try:
            loop.wait(timeout=STOP_S)
        except Exception as exc:               # the engine loop failed
            log(f"engine loop ended with {type(exc).__name__}: {exc}")
        engine.fail_all(RuntimeError("the benchmark window has closed"))
        app.stop()
    run = Run(cell=cell, seconds=seconds, t0=t0, t1=t1,
              setup_s=setup_s, loop=cell.traffic["loop"], requests=recs,
              schedule=sched, tokens_in_window=tokens,
              counts=counts(cell.config), peaks=peaks,
              prefills=probe.prefills, decodes=probe.decodes,
              engine_times=probe.engine_times)
    if tracer is not None:
        from . import devtrace as trace_mod
        run.trace = trace_mod.load(tracer.dir)
        run.trace_host = tracer.host
        shutil.rmtree(tracer.dir, ignore_errors=True)
    return run


def free_device(jax: Any) -> None:
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def execute(workload: str, seed: int, seconds: float, traced: bool,
            t_start: float, allow_cpu: bool = False, root: Path = ROOT,
            base: Path = HERE) -> dict:
    """The whole run; returns the result object (``correct`` ... ``checks``)."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache, watch_compiles

    cell = load_cell(workload, root, base)
    peaks_all = json.loads((base / "peaks.json").read_text())["devices"]
    device = device_info(jax, cell.chips, peaks_all, allow_cpu)
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"

    def log(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    log(f"cell {workload} seed {seed} seconds {seconds} trace {int(traced)}")
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache_dir}")
    compiles = watch_compiles()
    peaks = peaks_all.get(device["kind"], {})
    run = serve_window(cell, seed, seconds, traced, t_start, peaks, log,
                       compiles)
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell.chips])
    free_device(jax)
    checks = check(run, cell, seed)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        value = reader(base, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": passed(checks),
              "attempted": len(run.attempted),
              "failed": int(checks["unanswered"]["value"]),
              "metrics": metrics, "device": device}
    if traced:
        from . import devtrace as trace_mod
        tr = run.trace
        device["busy_s"] = trace_mod.device_busy_s(tr) if tr else 0.0
        device["window_s"] = (tr.window[1] - tr.window[0]) / 1e9 if tr else 0.0
        if tr is not None:
            result["breakdown"] = trace_mod.breakdown(tr)
    result["checks"] = checks
    return result


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None,
         allow_cpu: bool = False, root: Path = ROOT, base: Path = HERE) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start, allow_cpu, root, base)
    except BenchError as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
