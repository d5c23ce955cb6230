"""The traced window, for any serving path: the profiler on around the
window's run, zero-length marks at the window's open and close, and the
trace reduced to the window once the profiler has stopped.

    with session.Session(traced) as sess:
        ...                             # set-up ends, the traffic starts
        sess.mark(trace.OPEN)           # or, on an event loop:
        ...                             #   await sess.marks(t0, seconds)
        sess.mark(trace.CLOSE)
    run.trace = sess.summary            # None unless traced

Untraced, the session does nothing and its marks are no-ops.
"""

from __future__ import annotations

import asyncio
import shutil
import tempfile
import time

from harness import trace

CLOCK = time.perf_counter


def span(name: str):
    """A host span in the profiler's trace (nothing when it is off)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Session:
    def __init__(self, traced: bool) -> None:
        self.traced = traced
        self.summary: trace.Summary | None = None
        self._logdir: str | None = None

    def __enter__(self) -> "Session":
        if self.traced:
            import jax

            self._logdir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._logdir,
                                     profiler_options=trace.options())
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if not self.traced:
            return False
        import jax

        try:
            jax.profiler.stop_trace()
            if exc_type is None:
                self.summary = trace.reduce(trace.extract(
                    trace.xplane_file(self._logdir)))
        finally:
            shutil.rmtree(self._logdir, ignore_errors=True)
        return False

    def mark(self, name: str) -> None:
        """A zero-length span, ``trace.OPEN`` or ``trace.CLOSE``, that the
        reduction cuts the trace at."""
        if self.traced:
            with span(name):
                pass

    async def marks(self, t0: float, seconds: float) -> None:
        """Mark the window's open at ``t0`` and its close ``seconds`` later
        (``CLOCK`` times), sleeping on the event loop in between."""
        await asyncio.sleep(max(0.0, t0 - CLOCK()))
        self.mark(trace.OPEN)
        await asyncio.sleep(max(0.0, t0 + seconds - CLOCK()))
        self.mark(trace.CLOSE)
