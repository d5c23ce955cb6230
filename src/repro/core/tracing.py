"""Host spans of the serving path, on the JAX profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: it lands in the profiler's
own trace beside the device's operations, and it is recorded only while a
profiler session runs (``jax.profiler.trace(dir)``).  With no session a span
costs about a microsecond.  There is no buffer, exporter or switch here.

Stats ride as keyword arguments (``span(FLUSH, tenant="a", n=3)``) and show
in the trace as the event's stats.  Every name starts with ``mafia.``.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span", "mark", "POLL", "FLUSH", "RESTORE", "STACK", "PUT",
           "FORWARD", "FETCH", "SLICE", "SCATTER", "GC", "COMPILE",
           "LM_PREFILL", "LM_INSERT", "LM_DECODE", "LM_DECODE_FORWARD",
           "LM_DECODE_FETCH", "LM_DECODE_SAMPLE"]

POLL = "mafia.poll"                  # one scheduling round
FLUSH = "mafia.flush"                # one batched forward of one tenant
RESTORE = "mafia.flush.restore"      # artifact-store load or recompile
STACK = "mafia.flush.stack"          # rows stacked into one array
PUT = "mafia.batch.put"              # pad to the bucket (numpy, on the host)
FORWARD = "mafia.batch.forward"      # jitted forward's dispatch, host->device
FETCH = "mafia.flush.fetch"          # device wait, every output's copy at once
SLICE = "mafia.batch.slice"          # padding rows sliced off on the host
SCATTER = "mafia.flush.scatter"      # answers, futures and counters
GC = "mafia.gc"                      # an interpreter collection
COMPILE = "mafia.compile"            # an XLA program built or loaded
# the token engine (repro.serve.engine.ServeEngine)
LM_PREFILL = "mafia.lm.prefill"      # one prompt's forward and first token
LM_INSERT = "mafia.lm.insert"        # its cache rows written into the slot
LM_DECODE = "mafia.lm.decode"        # one batched decode step
LM_DECODE_FORWARD = "mafia.lm.decode.forward"   # the step's dispatch
LM_DECODE_FETCH = "mafia.lm.decode.fetch"       # device wait, tokens copied
LM_DECODE_SAMPLE = "mafia.lm.decode.sample"     # tokens to requests, retire


def span(name: str, **stats) -> TraceAnnotation:
    """A host span named ``name`` with ``stats``; use it with ``with``."""
    return TraceAnnotation(name, **stats)


def mark(name: str, **stats) -> None:
    """A zero-length span: an instant worth finding in the trace."""
    with TraceAnnotation(name, **stats):
        pass
