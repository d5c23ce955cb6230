"""The program's own record of each flush, matched to the window's flushes.

The engine keeps its recent flushes per tenant
(``ServeMetrics.recent_flushes``): rows carried, how late the loop came to
a due queue, and the host seconds of each phase, read from the same clock
readings as its ``mafia.*`` spans.  The benchmark's flush records of a
tenant (``run.flushes``) are the last of the engine's, in the same order:
set-up's flushes come before them, and none comes after, since every client
has its answer before the engine stops.  A program that keeps no such
record reads None.
"""

from __future__ import annotations

import numpy as np

from harness.stats import quantile


def _offset(rec_n: np.ndarray, n: np.ndarray) -> int | None:
    """Where the tenant's recorded flushes start among the engine's: the
    last place whose rows match, flush for flush."""
    for o in range(len(rec_n) - len(n), -1, -1):
        if np.array_equal(rec_n[o:o + len(n)], n):
            return o
    return None


def window(run) -> dict[str, np.ndarray] | None:
    """Each field of the engine's flush records over the flushes that
    started in the window, all tenants together."""
    parts = []
    for k, t in enumerate(run.tenants):
        recent = getattr(t.state.metrics, "recent_flushes", None)
        if recent is None:
            return None
        rec = recent()
        mine = run.flushes["tenant"] == k
        n = run.flushes["n"][mine]
        o = _offset(rec["n"], n)
        if o is None:
            return None
        m = run.in_window(run.flushes["t0"][mine])
        parts.append({f: v[o:o + len(n)][m] for f, v in rec.items()})
    if not parts:
        return None
    return {f: np.concatenate([p[f] for p in parts]) for f in parts[0]}


def median_ms(run, *fields: str) -> float | None:
    """Median over the window's flushes of the sum of ``fields``, in ms."""
    w = window(run)
    if w is None or len(w["n"]) == 0:
        return None
    return quantile(sum(w[f] for f in fields) * 1e3, 0.5)
