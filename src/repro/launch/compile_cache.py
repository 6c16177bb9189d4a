"""JAX's persistent compilation cache, switched on by the entry points.

``chip_smoke.py``, ``repro.launch.serve`` and ``repro.launch.train`` call
:func:`enable_compile_cache` once, before their first compile.  Nothing calls
it at import, and the tests leave it off.
"""
from __future__ import annotations

import os
from collections import Counter
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"

_COUNTED = {"/jax/compilation_cache/cache_hits": "hits",
            "/jax/compilation_cache/cache_misses": "misses"}
_TIMED = {"/jax/core/compile/backend_compile_duration": "compile_s"}


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    no other directory is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, so that the next run of the same
    checkout finds what this one compiled.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir


def watch_compiles() -> Counter:
    """From now on, count persistent-cache ``hits`` and ``misses`` (a miss is
    compiled, and written back when it took long enough) and sum
    ``compile_s``, the seconds spent in XLA's compile step."""
    stats: Counter = Counter()

    def on_event(event: str, **_: object) -> None:
        if event in _COUNTED:
            stats[_COUNTED[event]] += 1

    def on_duration(event: str, seconds: float, **_: object) -> None:
        if event in _TIMED:
            stats[_TIMED[event]] += seconds

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return stats
