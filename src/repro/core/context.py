"""First-class request context threaded through the send path.

A :class:`RequestContext` is the single carrier for everything a request
needs to flow end-to-end: the *session* identity that shard pinning keys
on, the absolute *deadline* that every nested hop tightens against, the
*depth* of the hop chain, and a *trace_id* that labels the whole tree.
The load generator creates one per request (``repro.core.loadgen``
builds it from the workload factory's session field) and ``App.send``
threads it through delivery, the handler, and every nested call — both
the carrier path and the zero-handoff inline fast path.

The plain path stays zero-overhead by construction:
:meth:`RequestContext.hop` returns ``None`` when there is neither a
parent context nor a deadline to carry, so ``send(dest, method,
payload)`` never allocates a context object.
"""

from __future__ import annotations

import itertools
import zlib
from typing import Optional, Union

__all__ = ["RequestContext", "session_key", "trace_ticket"]

SessionId = Union[str, bytes, int, None]

#: Process-wide trace ticket source (atomic under the GIL).
_trace_ticket = itertools.count(1)


def trace_ticket() -> int:
    """A fresh trace id from the source every :class:`RequestContext`
    draws from, for a request that arrived without a context."""
    return next(_trace_ticket)


def session_key(session: SessionId) -> int:
    """Deterministic non-negative integer key for a session id.

    Uses CRC32 for strings/bytes rather than the builtin ``hash`` because
    the latter is randomized per process — shard pinning must agree
    across trials, app restarts, and interpreter runs.  Integers pass
    through unchanged; ``None`` maps to 0.
    """
    if session is None:
        return 0
    if isinstance(session, int):
        return session & 0xFFFFFFFF
    if isinstance(session, str):
        session = session.encode("utf-8", "surrogatepass")
    return zlib.crc32(session) & 0xFFFFFFFF


def _min_dl(a: Optional[float], b: Optional[float]) -> Optional[float]:
    # Local copy of resilience.min_deadline to keep this module leaf-level
    # (resilience imports nothing from here, but context must stay
    # importable before anything else in repro.core).
    if a is None:
        return b
    if b is None:
        return a
    return a if a <= b else b


class RequestContext:
    """Per-request carrier: session, absolute deadline, hop depth, trace.

    Immutable by convention: interpreters never mutate a context in
    place, they derive a child via :meth:`hop` at each nested call so a
    parent's view of its own deadline/depth is never clobbered by a
    child hop running on another thread.
    """

    __slots__ = ("session", "deadline", "depth", "trace_id")

    def __init__(
        self,
        session: SessionId = None,
        deadline: Optional[float] = None,
        depth: int = 0,
        trace_id: Optional[int] = None,
    ) -> None:
        self.session = session
        self.deadline = deadline
        self.depth = depth
        self.trace_id = next(_trace_ticket) if trace_id is None else trace_id

    @classmethod
    def hop(
        cls,
        parent: Optional["RequestContext"],
        deadline: Optional[float] = None,
    ) -> Optional["RequestContext"]:
        """Context for one nested hop: inherit session/trace, tighten the
        deadline, increment depth.  Returns ``None`` when there is nothing
        to carry (no parent, no deadline) — the zero-alloc plain path."""
        if parent is None:
            if deadline is None:
                return None
            return cls(deadline=deadline, depth=1)
        return cls(
            session=parent.session,
            deadline=_min_dl(parent.deadline, deadline),
            depth=parent.depth + 1,
            trace_id=parent.trace_id,
        )

    def session_shard(self, n_shards: int) -> int:
        """Deterministic shard index for this context's session."""
        return session_key(self.session) % n_shards

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return ("RequestContext(session=%r, deadline=%r, depth=%d, "
                "trace_id=%r)" % (self.session, self.deadline, self.depth,
                                  self.trace_id))
