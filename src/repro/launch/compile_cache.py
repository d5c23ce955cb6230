"""JAX's persistent compilation cache at a path that does not move, and a
count of the XLA programs the process builds or loads.

The cache directory is part of what a cached executable is found under, so
it must be the same from one run to the next: ``$JAX_COMPILATION_CACHE_DIR``
where it is set (JAX reads it itself, and nothing else is set here), else
``.jax_cache`` at the root of this checkout.  Entry points call
:func:`enable_compile_cache` from ``main()``; importing this module changes
nothing.

:func:`enable_compile_cache` also starts the count that :func:`counts`
reads.  JAX reports each XLA program it builds or loads from the persistent
cache as a ``/jax/core/compile/backend_compile_duration`` event (a load is
preceded by a ``/jax/compilation_cache/cache_hits`` event); each one adds to
the count and leaves a zero-length ``mafia.compile`` span in the profiler's
trace, with the function's name, the seconds and whether it was a load.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

from repro.core import tracing

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache", "count_compiles",
           "counts"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# JAX's listeners are process-wide, and so is the count; a program may be
# compiled on any thread, which sees its own cache-hit event first.
_counts = {"programs": 0, "cache_hits": 0, "compile_s": 0.0}
_lock = threading.Lock()
_thread = threading.local()
_listening = False


def _on_event(event: str, **kw) -> None:
    if event == _HIT_EVENT:
        _thread.hit = True


def _on_duration(event: str, secs: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    hit = getattr(_thread, "hit", False)
    _thread.hit = False
    with _lock:
        _counts["programs"] += 1
        _counts["cache_hits"] += hit
        _counts["compile_s"] += secs
    tracing.mark(tracing.COMPILE, fun=str(kw.get("fun_name", "")),
                 secs=secs, hit=int(hit))


def count_compiles() -> None:
    """Start counting XLA programs (once per process)."""
    global _listening
    if _listening:
        return
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _listening = True


def counts() -> dict:
    """XLA programs built or loaded since :func:`enable_compile_cache`:
    ``programs``, of them ``cache_hits`` (loads from the persistent cache),
    and ``compile_s``, the seconds they took."""
    with _lock:
        return dict(_counts)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and start counting XLA
    programs; returns the cache's directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the serving path compiles many small kernels well under JAX's default
    # one-second floor; cache every one of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    count_compiles()
    return path
