"""Instrumentation seam for the concurrency sanitizer (``repro.analysis``).

``hooks`` is a **module-level hook table**: ``None`` in production, an
object with event methods when a sanitizer is attached.  Every call site in
``repro.core`` guards with a single branch::

    h = instrument.hooks
    if h is not None:
        h.future_set(fut)

so the disabled cost is one module-attribute load plus one ``is not None``
test — no indirection, no allocation, no lock.  The rpc_path micro bench
carries a paired probe (:func:`benchmarks.bench_rpc_path.measure_rpc_cost`
with ``hooks`` on/off) proving the seam stays inside the noise band when
off.

The event vocabulary is the :class:`Hooks` base class below; all methods
are no-ops so a subscriber overrides only what it consumes.  Events are
emitted **on the thread where the action happens** — subscribers derive
carrier identity from ``threading.get_ident()`` and must be thread-safe.

Design rules for call sites (keep the fast path honest):

* never emit from the zero-handoff inline path's per-call loop — inline
  calls synchronize nothing, so there is no edge to record;
* blocking/parking sites may emit freely (they already pay kernel sync);
* per-event payloads are existing objects (no tuples built when disabled).

This module is a leaf: it imports nothing from ``repro.core`` so every
core module can import it without cycles, and it keeps ``repro.core``
importable without ``repro.analysis`` (the analysis package depends on
core, never the reverse).
"""
from __future__ import annotations

from typing import Any, Optional

#: The hook table.  ``None`` (the overwhelmingly common case) disables the
#: seam; :func:`install` swaps in a :class:`Hooks` subclass.
hooks: Optional["Hooks"] = None


class Hooks:
    """No-op event sink; subclass and override the events you consume.

    One method per seam event.  Grouped by emitting module:

    ``repro.core.future``
        :meth:`future_set`, :meth:`future_block`, :meth:`future_unblock`
    ``repro.core.fiber``
        :meth:`fiber_spawn`, :meth:`fiber_park`, :meth:`fiber_resume`,
        :meth:`fiber_steal`, :meth:`sched_loop`, :meth:`queue_put`,
        :meth:`queue_take`, :meth:`ring_submit`, :meth:`ring_drain`
    ``repro.core.eventloop``
        :meth:`loop_spawn`, :meth:`queue_put`, :meth:`queue_take`,
        :meth:`sched_loop`, :meth:`shard_handoff`
    ``repro.core.timers``
        :meth:`timer_arm`, :meth:`timer_fire`, :meth:`timer_cancel`
    ``repro.core.executor``
        :meth:`carrier_start`, :meth:`carrier_stop`, :meth:`ring_submit`,
        :meth:`ring_drain`
    ``repro.core.service`` / ``repro.core.loadgen`` / ``repro.core.metrics``
        :meth:`stop_phase`, :meth:`trial_sever`, :meth:`recorder_write`,
        :meth:`recorder_summary`
    anyone (self-tests, lock proxies)
        :meth:`lock_acquire`, :meth:`lock_release`, :meth:`access`
    ``repro.serving`` (the LLM server's request path and engine loop)
        :meth:`span_begin`, :meth:`span_end`, :meth:`event`
    """

    # ------------------------------------------------------------- futures
    def future_set(self, fut: Any) -> None:
        """``fut`` just resolved (value/exception published)."""

    def future_block(self, fut: Any, timeout: Optional[float]) -> None:
        """A thread is about to *block* on ``fut`` (kernel wait)."""

    def future_unblock(self, fut: Any, done: bool) -> None:
        """A blocking wait on ``fut`` returned (``done=False`` = timeout)."""

    def future_join(self, fut: Any) -> None:
        """A cooperative carrier parked a continuation on ``fut``."""

    # -------------------------------------------------------------- fibers
    def fiber_spawn(self, sched: Any, fib: Any) -> None:
        """``fib`` (with its carrier ``fib.future``) queued on ``sched``."""

    def fiber_park(self, sched: Any, fib: Any) -> None:
        """``fib`` suspended awaiting futures/timers."""

    def fiber_resume(self, sched: Any, fib: Any) -> None:
        """``fib`` re-enqueued for execution."""

    def fiber_steal(self, victim: Any, thief: Any, n: int) -> None:
        """``thief`` stole ``n`` ready fibers from ``victim``."""

    def sched_loop(self, sched: Any) -> None:
        """A scheduler run loop claimed the current thread as its carrier."""

    # --------------------------------------------- run/injection queues
    def queue_put(self, obj: Any) -> None:
        """Work posted to ``obj``'s cross-thread queue (release edge)."""

    def queue_take(self, obj: Any) -> None:
        """``obj``'s owner drained its cross-thread queue (acquire edge)."""

    # ---------------------------------------------------------- event loop
    def loop_spawn(self, loop: Any, fut: Any) -> None:
        """A continuation producing ``fut`` was created on ``loop``."""

    def shard_handoff(self, loop: Any, shard: int) -> None:
        """A request was routed to shard ``shard`` of ``loop``."""

    # -------------------------------------------------------------- timers
    def timer_arm(self, owner: Any, deadline: float) -> None:
        """A timer entry became pending on ``owner``."""

    def timer_fire(self, owner: Any, n: int) -> None:
        """``owner`` popped ``n`` due entries."""

    def timer_cancel(self, owner: Any, n: int) -> None:
        """``owner`` dropped ``n`` pending entries without firing them."""

    # ------------------------------------------------------ carriers/rings
    def carrier_start(self, owner: Any, name: str) -> None:
        """``owner`` spawned carrier thread ``name``."""

    def carrier_stop(self, owner: Any) -> None:
        """``owner`` finished joining its carrier threads."""

    def ring_submit(self, ring: Any) -> None:
        """An entry was appended to a submission/completion ring."""

    def ring_drain(self, ring: Any, n: int, reason: str) -> None:
        """``n`` entries left ``ring`` (``reason``: size/timeout/idle/...)."""

    # -------------------------------------------------- app/trial protocol
    def stop_phase(self, app: Any, phase: str) -> None:
        """``App.stop`` entered the named shutdown phase."""

    def trial_sever(self, recorder: Any) -> None:
        """A load-gen trial severed late completions from ``recorder``."""

    def recorder_write(self, recorder: Any) -> None:
        """A latency sample/error landed in ``recorder``."""

    def recorder_summary(self, recorder: Any) -> None:
        """``recorder``'s summary statistics were read."""

    # ------------------------------------------- generic sanitizer surface
    def lock_acquire(self, key: Any) -> None:
        """The current thread acquired the lock identified by ``key``."""

    def lock_release(self, key: Any) -> None:
        """The current thread released the lock identified by ``key``."""

    def access(self, key: Any, write: bool) -> None:
        """The current thread touched shared state ``key`` (race check)."""

    # ------------------------------------------------------------- spans
    def span_begin(self, name: str, rid: Optional[int]) -> None:
        """The span ``name`` of request ``rid`` opened (``rid`` None: the
        engine loop's own work, or the host's)."""

    def span_end(self, name: str, rid: Optional[int],
                 attrs: Optional[dict]) -> None:
        """The open span ``name`` of ``rid`` closed; ``attrs`` are numbers
        known only at its end (or None)."""

    def event(self, name: str, rid: Optional[int],
              attrs: Optional[dict]) -> None:
        """An instant ``name`` in request ``rid``."""


def install(h: Hooks) -> None:
    """Attach a hook table (replacing any previous one)."""
    global hooks
    hooks = h


def uninstall() -> None:
    """Detach the hook table; the seam reverts to the single dead branch."""
    global hooks
    hooks = None
