"""The program's own spans in a traced run: ``mafia.*`` host events.

``extract`` reads them, with their stats, from an ``.xplane.pb``.  Beside
what ``trace.extract`` keeps (device operations and launches, the
benchmark's ``bench.*`` spans), they split each flush into its phases on the
clock of the device's operations:

* ``by_flush`` gives each ``mafia.flush`` of the window the child spans it
  contains, summed by name;
* ``idle_innermost`` puts each idle interval of the device down to the
  innermost span covering it, among ``bench.*`` and ``mafia.*``, so that
  ``bench.flush`` keeps only what no program span covers.

Everything is in nanoseconds on the trace's clock, as ``trace.extract``
gives it; the window is the benchmark's two marks.
"""

from __future__ import annotations

import numpy as np

from harness import trace

PREFIX = "mafia."
FLUSH = "mafia.flush"
NO_SPAN = "no span"


def extract(path) -> list:
    """The ``mafia.*`` host events of a trace as ``[name, start_ns,
    duration_ns, stats]``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend([e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def window(ex: dict) -> tuple[float, float]:
    marks = {name: start for name, start, _ in ex["host"]
             if name in (trace.OPEN, trace.CLOSE)}
    return float(marks[trace.OPEN]), float(marks[trace.CLOSE])


def in_window(ex: dict) -> list:
    """The program spans that start in the window, as ``(name, start_s
    from the window's open, duration_s, stats)``."""
    lo, hi = window(ex)
    return [(n, (s - lo) * 1e-9, d * 1e-9, st)
            for n, s, d, st in sorted(ex["spans"], key=lambda e: e[1])
            if lo <= s < hi]


def by_flush(spans: list) -> list[dict]:
    """For each ``mafia.flush`` of ``spans`` (as :func:`in_window` gives
    them), its duration, stats and the seconds of each child span inside
    it, summed by name."""
    flushes = [s for s in spans if s[0] == FLUSH]
    out = [{"start": s, "dur": d, "stats": st, "children": {}}
           for _, s, d, st in flushes]
    i = 0
    for name, s, d, _ in spans:
        if name == FLUSH or not name.startswith((FLUSH + ".", "mafia.batch.")):
            continue
        while i < len(out) and out[i]["start"] + out[i]["dur"] < s:
            i += 1
        if i < len(out) and out[i]["start"] <= s:
            ch = out[i]["children"]
            ch[name] = ch.get(name, 0.0) + d
    return out


def idle_innermost(ex: dict, top: int = 12) -> list[tuple[str, float]]:
    """Idle seconds of the device (mean over devices) by the innermost host
    span covering them, among ``bench.*`` and ``mafia.*``; the rest under
    ``no span``."""
    lo, hi = window(ex)
    host = [(n, s, d) for n, s, d in ex["host"]
            if n not in (trace.OPEN, trace.CLOSE)]
    host += [(n, s, d) for n, s, d, _ in ex["spans"]]
    edges = []
    for k, (_, s, d) in enumerate(host):
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            edges.append((a, 1, k))
            edges.append((b, 0, k))
    edges.sort()
    # cut the window at every span's edge; each piece goes to the innermost
    # span open over it (the latest to start, then the shortest)
    cuts, keys = [lo], []
    active: dict[int, float] = {}
    for x, opening, k in edges + [(hi, 0, -1)]:
        if x > cuts[-1]:
            inner = (max(active, key=lambda j: (active[j], -host[j][2]))
                     if active else None)
            keys.append(NO_SPAN if inner is None else host[inner][0])
            cuts.append(x)
        if opening:
            active[k] = host[k][1]
        else:
            active.pop(k, None)
    cuts = np.asarray(cuts)
    out: dict[str, float] = {}
    for events in ex["ops"].values():
        _, iv = trace._clip(events, lo, hi)
        busy = trace._union(iv)
        # idle time in [lo, t] as a piecewise-linear function of t
        bx = np.concatenate([[lo], busy.ravel(), [hi]])
        idle_to = np.concatenate([[0.0], np.cumsum(
            np.where(np.arange(len(bx) - 1) % 2 == 0, np.diff(bx), 0.0))])
        idle = np.diff(np.interp(cuts, bx, idle_to)) * 1e-9 / len(ex["ops"])
        for key, v in zip(keys, idle):
            out[key] = out.get(key, 0.0) + float(v)
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]
