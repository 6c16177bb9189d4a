"""Happens-before state for the dynamic sanitizer: vector clocks + races.

The model is the classic vector-clock data-race detector, adapted to the
runtime's event vocabulary instead of raw memory operations:

* every **thread** (kernel carrier, scheduler loop, timer thread, test
  thread) owns a vector clock, keyed by the sanitizer's own thread id;
* a **release edge** on a channel (mailbox put, ring submit, future set,
  fiber injection) joins the releasing thread's clock into the channel's
  clock, then advances the releaser;
* an **acquire edge** (mailbox take, ring drain, post-wait resume) joins
  the channel's clock into the acquiring thread's clock;
* a **shared-variable access** (``access(key, write)`` events) is checked
  against the variable's last-writer epoch and read map: any pair of
  accesses, at least one a write, on different threads, with neither
  ordered before the other, is a race.

This is FastTrack-lite: writes keep a single last-writer epoch (the
runtime's counters follow a single-writer-or-locked discipline, so a
write-write race already reports on the second write), reads keep a full
per-thread map (many readers are legal and must all be ordered before the
next write).

The state is *not* itself thread-safe — the sanitizer serializes all event
processing under one lock.  That lock creates real-time ordering but no
model-level edges, which is exactly what a dynamic race detector wants:
the analysis sees the interleaving that actually happened, and only the
edges the runtime explicitly emitted count as synchronization.
"""
from __future__ import annotations

from typing import Dict, Hashable, List, Optional, Tuple

# A vector clock: thread ident -> logical time.  Sparse (absent = 0).
Clock = Dict[int, int]


def _join(into: Clock, other: Clock) -> None:
    for tid, c in other.items():
        if into.get(tid, 0) < c:
            into[tid] = c


class RaceReport:
    """One detected race: the variable, both access epochs, the kind."""

    __slots__ = ("key", "kind", "prev_tid", "curr_tid")

    def __init__(self, key: str, kind: str, prev_tid: int,
                 curr_tid: int) -> None:
        self.key = key
        self.kind = kind            # "write-write" | "read-write" | "write-read"
        self.prev_tid = prev_tid
        self.curr_tid = curr_tid

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"RaceReport({self.key!r}, {self.kind}, "
                f"prev_tid={self.prev_tid}, curr_tid={self.curr_tid})")


class HBState:
    """Vector clocks per thread + per-channel clocks + per-variable epochs."""

    def __init__(self) -> None:
        self._clocks: Dict[int, Clock] = {}
        self._channels: Dict[Hashable, Clock] = {}
        # per shared variable: last write epoch and the clock snapshot the
        # writer held, plus every read epoch since that write
        self._last_write: Dict[str, Tuple[int, int]] = {}   # key -> (tid, c)
        self._write_clock: Dict[str, Clock] = {}
        self._reads: Dict[str, Dict[int, int]] = {}          # key -> tid -> c

    # ------------------------------------------------------------- clocks
    def _clock(self, tid: int) -> Clock:
        clk = self._clocks.get(tid)
        if clk is None:
            clk = self._clocks[tid] = {tid: 1}
        return clk

    def _tick(self, tid: int) -> None:
        clk = self._clock(tid)
        clk[tid] = clk.get(tid, 0) + 1

    def release(self, tid: int, channel: Hashable) -> None:
        """``tid`` publishes its history into ``channel`` (e.g. queue put,
        future set) and advances its own component."""
        clk = self._clock(tid)
        chan = self._channels.setdefault(channel, {})
        _join(chan, clk)
        self._tick(tid)

    def acquire(self, tid: int, channel: Hashable) -> None:
        """``tid`` adopts ``channel``'s history (e.g. queue take, post-wait
        resume): everything released into the channel now happens-before
        every subsequent action of ``tid``."""
        chan = self._channels.get(channel)
        if chan:
            _join(self._clock(tid), chan)

    def fork(self, parent_tid: int, channel: Hashable) -> None:
        """Synonym for :meth:`release` at a spawn point — the child's first
        acquire on the same channel inherits the parent's history."""
        self.release(parent_tid, channel)

    def drop_channel(self, channel: Hashable) -> None:
        """Forget a channel's clock (its object was garbage-collected)."""
        self._channels.pop(channel, None)

    # ------------------------------------------------------------ accesses
    def access(self, tid: int, key: str, write: bool) -> Optional[RaceReport]:
        """Record one access to shared variable ``key``; return the race it
        completes, if any (first race per access reported)."""
        clk = self._clock(tid)
        lw = self._last_write.get(key)
        if lw is not None:
            w_tid, w_c = lw
            if w_tid != tid and clk.get(w_tid, 0) < w_c:
                kind = "write-write" if write else "write-read"
                return RaceReport(key, kind, w_tid, tid)
        if write:
            report = None
            for r_tid, r_c in self._reads.get(key, {}).items():
                if r_tid != tid and clk.get(r_tid, 0) < r_c:
                    report = RaceReport(key, "read-write", r_tid, tid)
                    break
            self._last_write[key] = (tid, clk.get(tid, 0))
            self._write_clock[key] = dict(clk)
            self._reads[key] = {}
            self._tick(tid)
            return report
        self._reads.setdefault(key, {})[tid] = clk.get(tid, 0)
        return None

    # ----------------------------------------------------------- introspect
    def ordered_before(self, a_tid: int, a_c: int, b_tid: int) -> bool:
        """True iff epoch ``(a_tid, a_c)`` happened-before ``b_tid``'s now."""
        return self._clock(b_tid).get(a_tid, 0) >= a_c

    def threads(self) -> List[int]:
        """Idents of every thread the state has seen."""
        return list(self._clocks)
