"""From the profiler's trace to device numbers.

``extract`` reads an ``.xplane.pb`` into plain lists: the device's
operations (``XLA Ops`` lines), the device's program launches (``XLA
Modules`` lines) and the benchmark's own host spans (``bench.*``).
``reduce`` cuts them to the measured window, which the harness marks with
two zero-length spans, and computes:

* busy time: the union of the operation intervals on each device,
  averaged over devices, and the idle share of the window;
* the device operations that took most time;
* idle time by what the host was doing: each idle interval of the device is
  split over the benchmark's host spans that overlap it, the rest is time
  in no span of the benchmark (the event loop, its sleep, client code);
* the device time of the forward: launches of the program whose name the
  caller gives.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

OPEN, CLOSE = "bench.window_open", "bench.window_close"
NO_SPAN = "no bench span"


def options():
    """Profiler options: host spans on, the Python tracer off (it would
    record every Python call of the serving loop)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def xplane_file(logdir: str | Path) -> Path:
    files = glob.glob(str(Path(logdir) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {logdir}, "
                                f"found {files}")
    return Path(files[0])


def extract(path: str | Path) -> dict:
    """The events the reduction needs, as plain lists of
    ``[name, start_ns, duration_ns]`` (device events keyed by plane)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events)
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"ops": ops, "modules": modules, "host": host}


def load(path: str | Path) -> dict:
    """An extracted trace kept as JSON (the tests' recorded trace)."""
    return json.loads(Path(path).read_text())


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices
    n_devices: int
    top_ops: list[tuple[str, float]]   # seconds, summed over devices
    idle_by_host: list[tuple[str, float]]
    module_s: dict[str, float]         # device seconds per launched program

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_s(self, module_prefix: str) -> float:
        """Device seconds of the programs whose name starts with
        ``module_prefix``."""
        return sum(s for m, s in self.module_s.items()
                   if m.startswith(module_prefix))


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted, disjoint intervals covering the ``(n, 2)`` intervals ``iv``."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, dtype=np.float64)


_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")


def op_name(hlo: str) -> str:
    """A device operation's name as its instruction and opcode, from the
    HLO text the trace names it by: ``%launch.1 = (...) custom-call(...)``
    reads ``launch.1 custom-call``."""
    left, sep, right = hlo.partition(" = ")
    if not sep:
        return hlo
    m = _OPCODE.search(" " + right)
    return f"{left.lstrip('%')} {m.group(1)}" if m else left.lstrip("%")


def _clip(events: list, lo: float, hi: float) -> tuple[list, np.ndarray]:
    names, iv = [], []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            names.append(name)
            iv.append((a, b))
    return names, np.asarray(iv, dtype=np.float64).reshape(-1, 2)


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total overlap of two sorted disjoint interval sets."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


def reduce(ex: dict, top: int = 10) -> Summary:
    marks = {name: start for name, start, _ in ex["host"]
             if name in (OPEN, CLOSE)}
    if OPEN not in marks or CLOSE not in marks:
        raise ValueError("the trace holds no window marks")
    lo, hi = float(marks[OPEN]), float(marks[CLOSE])
    if not ex["ops"]:
        raise ValueError("the trace holds no device operations")
    busy_total = 0.0
    per_op: dict[str, float] = defaultdict(float)
    idle_sets = []
    for plane, events in ex["ops"].items():
        names, iv = _clip(events, lo, hi)
        for name, (a, b) in zip(names, iv):
            per_op[op_name(name)] += (b - a) * 1e-9
        busy = _union(iv)
        busy_total += float(np.sum(busy[:, 1] - busy[:, 0]))
        edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
        idle_sets.append(edges[edges[:, 1] > edges[:, 0]])
    n_dev = len(ex["ops"])
    host_names = sorted({n for n, _, _ in ex["host"]} - {OPEN, CLOSE})
    idle: dict[str, float] = defaultdict(float)
    for gaps in idle_sets:
        gap_total = float(np.sum(gaps[:, 1] - gaps[:, 0]))
        covered = 0.0
        for name in host_names:
            _, iv = _clip([e for e in ex["host"] if e[0] == name], lo, hi)
            ov = _overlap(gaps, _union(iv))
            idle[name] += ov * 1e-9 / n_dev
            covered += ov
        idle[NO_SPAN] += max(0.0, gap_total - covered) * 1e-9 / n_dev
    module_s: dict[str, float] = defaultdict(float)
    for events in ex["modules"].values():
        names, iv = _clip(events, lo, hi)
        for name, (a, b) in zip(names, iv):
            module_s[name] += (b - a) * 1e-9 / n_dev
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 / n_dev,
        n_devices=n_dev,
        top_ops=sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        idle_by_host=sorted(idle.items(), key=lambda kv: -kv[1])[:top],
        module_s=dict(module_s))
