"""Dynamic concurrency sanitizer: the consumer of ``repro.core.instrument``.

:class:`Sanitizer` is a :class:`~repro.core.instrument.Hooks`
implementation.  Installed via :func:`attached` (or raw
``instrument.install``), it folds the runtime's event stream into four
detectors:

``SAN-RACE``
    Unsynchronized shared-variable access: ``access(key, write)`` events
    checked against the vector-clock happens-before state
    (:mod:`repro.analysis.hb`).  Mailbox put/take, ring submit/drain,
    future set/resume, fiber steal and timer arm/fire events are the
    synchronization edges; anything else concurrent is a race.

``SAN-LOCK-ORDER``
    Lock-acquisition-order cycles (:mod:`repro.analysis.lockgraph`), fed
    by ``lock_acquire``/``lock_release`` events — usually emitted by the
    :class:`TrackedLock` / :class:`TrackedCondition` proxies that
    :func:`track_app_locks` swaps onto a live app's locks.

``SAN-FUT-LEAK``
    Futures somebody *awaited* — a cooperative ``Wait`` park
    (``future_join``) or an untimed blocking ``Future.wait``
    (``future_block(timeout=None)``) — that are still unresolved when
    :meth:`Sanitizer.check` runs: a lost wakeup or a leaked blackhole.
    Timed blocking waits are excluded (the waiter owned a recovery path).

``SAN-TRIAL-SUMMARY``
    The loadgen trial-isolation protocol (PR 6): a
    ``LatencyRecorder`` write arriving *after* the recorder's summary was
    read while the trial had not yet been severed means the summary raced
    a late completion; a write after ``trial_sever`` means the sever
    failed to freeze the recorder.  Either ordering is the PR 6 bug.

``SAN-SELF-DEADLOCK`` (warn tier this PR)
    A thread blocking on a :class:`~repro.core.future.Future` whose only
    producer is a scheduler *owned by that same thread* — the producer
    can never run while its carrier is blocked.  Reported as a warning
    until a full PR of soak coverage upgrades it (see docs/ANALYSIS.md).

Scope and cost
--------------
The sanitizer is a **test-time** tool: all event processing serializes
under one internal lock, and object identity is tracked by ``id()`` (safe
for test-scoped attachment windows; a detached sanitizer drops its
references).  Production runs never install hooks and pay one untaken
branch per event site.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from repro.core import instrument

from .hb import HBState
from .lockgraph import LockOrderGraph

_WARN_RULES = frozenset({"SAN-SELF-DEADLOCK"})


@dataclass
class Finding:
    """One sanitizer finding: rule id, severity tier, human message."""

    rule: str
    message: str
    severity: str = "error"  # "error" | "warn"

    def __str__(self) -> str:
        return f"[{self.severity}] {self.rule}: {self.message}"


class Sanitizer(instrument.Hooks):
    """Happens-before + lock-order + leak detectors over the event seam."""

    def __init__(self) -> None:
        self._mu = threading.RLock()
        self._thread = threading.local()
        self._tids = itertools.count(1)
        self.hb = HBState()
        self.lockgraph = LockOrderGraph()
        self.counts: Counter = Counter()
        self.findings: List[Finding] = []
        # futures awaited without a recovery path: id -> (fut, how)
        self._awaited: Dict[int, Tuple[Any, str]] = {}
        # producer scheduler per future (id -> scheduler id), and the
        # kernel thread that owns each scheduler loop (scheduler id -> tid)
        self._producer: Dict[int, int] = {}
        self._owner: Dict[int, int] = {}
        # LatencyRecorder protocol state: id -> {"summary", "severed"}
        self._recorders: Dict[int, Dict[str, bool]] = {}
        # App.stop phase order per app id, for shutdown-ordering audits
        self._stop_phases: Dict[int, List[str]] = {}
        self._dedup: set = set()

    # ------------------------------------------------------------ plumbing
    def _flag(self, rule: str, message: str, *, dedup: Optional[str] = None
              ) -> None:
        if dedup is not None:
            if dedup in self._dedup:
                return
            self._dedup.add(dedup)
        sev = "warn" if rule in _WARN_RULES else "error"
        self.findings.append(Finding(rule, message, sev))

    def _tid(self) -> int:
        """The calling thread's id, never given to another thread:
        ``threading.get_ident()`` is reused by a thread started after one
        ended, which would make their accesses look ordered."""
        tid = getattr(self._thread, "tid", None)
        if tid is None:
            tid = self._thread.tid = next(self._tids)
        return tid

    # ------------------------------------------------------------- futures
    def future_set(self, fut: Any) -> None:
        with self._mu:
            self.counts["future_set"] += 1
            self.hb.release(self._tid(), id(fut))

    def future_block(self, fut: Any, timeout: Optional[float]) -> None:
        with self._mu:
            self.counts["future_block"] += 1
            if timeout is None:
                self._awaited[id(fut)] = (fut, "blocking wait")
            sched = self._producer.get(id(fut))
            if (sched is not None and not fut.done
                    and self._owner.get(sched) == self._tid()):
                self._flag(
                    "SAN-SELF-DEADLOCK",
                    "blocking Future.wait on the scheduler thread that owns "
                    "the future's only producer: the producer fiber can "
                    "never run while its carrier thread is blocked "
                    "(yield Wait(...) instead of calling wait())",
                    dedup=f"selfdl:{id(fut)}")

    def future_unblock(self, fut: Any, done: bool) -> None:
        with self._mu:
            self.counts["future_unblock"] += 1
            if done:
                self.hb.acquire(self._tid(), id(fut))

    def future_join(self, fut: Any) -> None:
        with self._mu:
            self.counts["future_join"] += 1
            if not fut.done:
                self._awaited[id(fut)] = (fut, "cooperative Wait park")

    # -------------------------------------------------------------- fibers
    def fiber_spawn(self, sched: Any, fib: Any) -> None:
        with self._mu:
            self.counts["fiber_spawn"] += 1
            fut = getattr(fib, "future", None)
            if fut is not None:
                self._producer[id(fut)] = id(sched)

    def fiber_park(self, sched: Any, fib: Any) -> None:
        with self._mu:
            self.counts["fiber_park"] += 1

    def fiber_resume(self, sched: Any, fib: Any) -> None:
        with self._mu:
            self.counts["fiber_resume"] += 1

    def fiber_steal(self, victim: Any, thief: Any, n: int) -> None:
        with self._mu:
            self.counts["fiber_steal"] += n

    def sched_loop(self, sched: Any) -> None:
        with self._mu:
            self.counts["sched_loop"] += 1
            self._owner[id(sched)] = self._tid()

    # ----------------------------------------------------- queues and rings
    def queue_put(self, obj: Any) -> None:
        with self._mu:
            self.counts["queue_put"] += 1
            self.hb.release(self._tid(), id(obj))

    def queue_take(self, obj: Any) -> None:
        with self._mu:
            self.counts["queue_take"] += 1
            self.hb.acquire(self._tid(), id(obj))

    def ring_submit(self, ring: Any) -> None:
        with self._mu:
            self.counts["ring_submit"] += 1
            self.hb.release(self._tid(), id(ring))

    def ring_drain(self, ring: Any, n: int, reason: str) -> None:
        with self._mu:
            self.counts["ring_drain"] += 1
            self.hb.acquire(self._tid(), id(ring))

    # --------------------------------------------------------- event loops
    def loop_spawn(self, loop: Any, fut: Any) -> None:
        with self._mu:
            self.counts["loop_spawn"] += 1
            self._producer[id(fut)] = id(loop)

    def shard_handoff(self, loop: Any, shard: Any) -> None:
        with self._mu:
            self.counts["shard_handoff"] += 1

    # --------------------------------------------------------------- timers
    def timer_arm(self, owner: Any, deadline: float) -> None:
        with self._mu:
            self.counts["timer_arm"] += 1
            self.hb.release(self._tid(), ("timer", id(owner)))

    def timer_fire(self, owner: Any, n: int) -> None:
        with self._mu:
            self.counts["timer_fire"] += n
            self.hb.acquire(self._tid(), ("timer", id(owner)))

    def timer_cancel(self, owner: Any, n: int) -> None:
        with self._mu:
            self.counts["timer_cancel"] += n

    # ------------------------------------------------------------- carriers
    def carrier_start(self, owner: Any, name: str) -> None:
        with self._mu:
            self.counts["carrier_start"] += 1

    def carrier_stop(self, owner: Any) -> None:
        with self._mu:
            self.counts["carrier_stop"] += 1

    # ---------------------------------------------------- lifecycle / trials
    def stop_phase(self, app: Any, phase: str) -> None:
        with self._mu:
            self.counts["stop_phase"] += 1
            self._stop_phases.setdefault(id(app), []).append(phase)

    def trial_sever(self, recorder: Any) -> None:
        with self._mu:
            self.counts["trial_sever"] += 1
            self._rec(recorder)["severed"] = True

    def recorder_write(self, recorder: Any) -> None:
        with self._mu:
            self.counts["recorder_write"] += 1
            st = self._rec(recorder)
            if st["severed"]:
                self._flag(
                    "SAN-TRIAL-SUMMARY",
                    "LatencyRecorder write after its trial was severed: the "
                    "sever failed to freeze the recorder (a late completion "
                    "escaped the liveness check)",
                    dedup=f"sever-write:{id(recorder)}")
            elif st["summary"]:
                self._flag(
                    "SAN-TRIAL-SUMMARY",
                    "LatencyRecorder write after its summary was read on a "
                    "live (unsevered) trial: the summary raced a late "
                    "completion — sever the trial before reading it "
                    "(loadgen.run_trial's sever-then-summarize order)",
                    dedup=f"summary-write:{id(recorder)}")

    def recorder_summary(self, recorder: Any) -> None:
        with self._mu:
            self.counts["recorder_summary"] += 1
            self._rec(recorder)["summary"] = True

    def _rec(self, recorder: Any) -> Dict[str, bool]:
        st = self._recorders.get(id(recorder))
        if st is None:
            st = self._recorders[id(recorder)] = {
                "summary": False, "severed": False}
        return st

    # ----------------------------------------------------- locks + accesses
    def lock_acquire(self, key: str) -> None:
        with self._mu:
            self.counts["lock_acquire"] += 1
            tid = self._tid()
            self.hb.acquire(tid, ("lock", key))
            self.lockgraph.acquire(tid, key)

    def lock_release(self, key: str) -> None:
        with self._mu:
            self.counts["lock_release"] += 1
            tid = self._tid()
            self.hb.release(tid, ("lock", key))
            self.lockgraph.release(tid, key)

    def access(self, key: str, write: bool) -> None:
        with self._mu:
            self.counts["access"] += 1
            race = self.hb.access(self._tid(), key, write)
            if race is not None:
                self._flag(
                    "SAN-RACE",
                    f"unsynchronized {race.kind} on {race.key!r} between "
                    f"threads {race.prev_tid} and {race.curr_tid}: no "
                    "happens-before edge orders the accesses (guard the "
                    "counter with its owner lock, or make it an "
                    "itertools.count ticket)",
                    dedup=f"race:{race.key}:{race.kind}")

    # --------------------------------------------------------------- report
    def stop_phases(self, app: Any) -> List[str]:
        """Shutdown phases observed for ``app``, in execution order."""
        with self._mu:
            return list(self._stop_phases.get(id(app), ()))

    def check(self) -> List[Finding]:
        """Finalize the run: fold in end-of-run detectors (leaked futures,
        lock-order cycles) and return every finding."""
        with self._mu:
            for fid, (fut, how) in list(self._awaited.items()):
                if not fut.done:
                    self._flag(
                        "SAN-FUT-LEAK",
                        f"future awaited ({how}) but never resolved: a lost "
                        "wakeup or a leaked blackhole (settle abandoned "
                        "replies at teardown — see FaultPlan."
                        "settle_blackholed and App.stop)",
                        dedup=f"leak:{fid}")
            for cyc in self.lockgraph.cycles():
                self._flag(
                    "SAN-LOCK-ORDER",
                    "lock-acquisition-order cycle "
                    + " -> ".join(cyc)
                    + ": two threads taking these locks in opposite orders "
                    "can deadlock (pick one global order and stick to it)",
                    dedup=f"cycle:{tuple(sorted(set(cyc)))}")
            return list(self.findings)

    def errors(self) -> List[Finding]:
        """Findings in the hard-fail tier (after :meth:`check`)."""
        return [f for f in self.findings if f.severity == "error"]

    def warnings(self) -> List[Finding]:
        """Findings in the warn tier (after :meth:`check`)."""
        return [f for f in self.findings if f.severity == "warn"]


# --------------------------------------------------------------------------
# lock proxies: feed SAN-LOCK-ORDER without touching production lock code
# --------------------------------------------------------------------------
class TrackedLock:
    """A named proxy around a real ``threading.Lock``/``RLock`` that emits
    ``lock_acquire``/``lock_release`` events.  Swap one onto a live object's
    lock attribute (see :func:`track_app_locks`) — with no hooks installed
    it degrades to one attribute load per operation."""

    __slots__ = ("_lock", "name")

    def __init__(self, lock: Any, name: str) -> None:
        self._lock = lock
        self.name = name

    def acquire(self, *args: Any, **kwargs: Any) -> bool:
        got = self._lock.acquire(*args, **kwargs)
        if got:
            h = instrument.hooks
            if h is not None:
                h.lock_acquire(self.name)
        return got

    def release(self) -> None:
        h = instrument.hooks
        if h is not None:
            h.lock_release(self.name)
        self._lock.release()

    def __enter__(self) -> "TrackedLock":
        self.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.release()

    def locked(self) -> bool:
        """Delegate liveness probe (tests use it)."""
        return self._lock.locked()


class TrackedCondition:
    """Same proxy for ``threading.Condition``: ``wait`` releases the lock
    (a ``lock_release`` event) and re-acquires it on wakeup, so the
    lock-order graph sees exactly what the kernel does."""

    __slots__ = ("_cond", "name")

    def __init__(self, cond: Any, name: str) -> None:
        self._cond = cond
        self.name = name

    def acquire(self, *args: Any, **kwargs: Any) -> bool:
        got = self._cond.acquire(*args, **kwargs)
        if got:
            h = instrument.hooks
            if h is not None:
                h.lock_acquire(self.name)
        return got

    def release(self) -> None:
        h = instrument.hooks
        if h is not None:
            h.lock_release(self.name)
        self._cond.release()

    def __enter__(self) -> "TrackedCondition":
        self.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.release()

    def wait(self, timeout: Optional[float] = None) -> bool:
        h = instrument.hooks
        if h is not None:
            h.lock_release(self.name)
        try:
            return self._cond.wait(timeout=timeout)
        finally:
            if h is not None:
                h.lock_acquire(self.name)

    def wait_for(self, predicate: Callable[[], bool],
                 timeout: Optional[float] = None) -> bool:
        h = instrument.hooks
        if h is not None:
            h.lock_release(self.name)
        try:
            return self._cond.wait_for(predicate, timeout=timeout)
        finally:
            if h is not None:
                h.lock_acquire(self.name)

    def notify(self, n: int = 1) -> None:
        self._cond.notify(n)

    def notify_all(self) -> None:
        self._cond.notify_all()


def track_app_locks(app: Any) -> Callable[[], None]:
    """Swap :class:`TrackedLock` proxies onto a live app's principal locks
    (service state locks, admission locks, the breaker-table lock) so the
    lock-order graph sees their acquisition order.  Returns a restore
    callable that puts the original locks back."""
    restores: List[Callable[[], None]] = []

    def swap(obj: Any, attr: str, name: str) -> None:
        orig = getattr(obj, attr)
        setattr(obj, attr, TrackedLock(orig, name))
        restores.append(lambda o=obj, a=attr, g=orig: setattr(o, a, g))

    for svc_name, svc in getattr(app, "services", {}).items():
        swap(svc, "lock", f"svc:{svc_name}.state")
        swap(svc, "_adm_lock", f"svc:{svc_name}.admission")
    if hasattr(app, "_breaker_lock"):
        swap(app, "_breaker_lock", "app.breaker_table")

    def restore() -> None:
        for r in reversed(restores):
            r()

    return restore


@contextlib.contextmanager
def attached(*, app: Any = None) -> Iterator[Sanitizer]:
    """Install a fresh :class:`Sanitizer` for the duration of the block.

    With ``app`` given, its principal locks are proxy-tracked too (and
    restored on exit).  The sanitizer is *not* checked automatically —
    call ``san.check()`` (and assert on ``san.errors()``) inside or after
    the block, while the objects under test are still alive."""
    san = Sanitizer()
    restore: Optional[Callable[[], None]] = None
    instrument.install(san)
    try:
        if app is not None:
            restore = track_app_locks(app)
        yield san
    finally:
        if restore is not None:
            restore()
        instrument.uninstall()
