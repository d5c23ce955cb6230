#!/usr/bin/env python3
"""A traced run of one cell, read by the program's own spans.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds 20 \
        [--cut-ms 30 --cut-out trace_cut.json]

Runs the cell as ``run.py --trace 1`` does and prints its result line, with
the end-to-end metrics beside the per-layer ones (what tracing costs them).
Then one more JSON line read from the ``mafia.*`` spans of the same trace:
the window's flushes as the benchmark counts them (``bench.flush``) and as
the program does (``mafia.flush``), the median of each phase of a flush,
the share of a flush its phases cover, how late the loop came to due
queues, collector pauses and compiles inside the window, and the device's
idle time by the innermost span covering it.  ``--cut-out`` keeps
``--cut-ms`` of the trace from the window's middle, as the tests' recorded
traces are kept.  Runs on the chip only, as ``run.py`` does.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402


def _median(x: list) -> float | None:
    from harness.stats import quantile

    return quantile(x, 0.5) if x else None


def summarize(ex: dict) -> dict:
    """The program spans' reading of an extracted trace (``ex`` as
    ``trace.extract`` gives it, with the ``mafia.*`` events under
    ``spans``)."""
    from harness import spans

    lo, hi = spans.window(ex)
    sp = spans.in_window(ex)
    flushes = spans.by_flush(sp)
    bench_flushes = [(s, d) for n, s, d in ex["host"]
                     if n == "bench.flush" and lo <= s < hi]
    starts = sorted(f["start"] * 1e9 + lo for f in flushes)
    paired = 0
    j = 0
    for s, d in sorted(bench_flushes):
        while j < len(starts) and starts[j] < s:
            j += 1
        if j < len(starts) and starts[j] <= s + d:
            paired += 1
            j += 1
    names = sorted({n for f in flushes for n in f["children"]})

    def med_ms(keys):
        return _median([sum(f["children"].get(k, 0.0) for k in keys) * 1e3
                        for f in flushes])

    flush_ms = _median([f["dur"] * 1e3 for f in flushes])
    covered_ms = med_ms(names)
    gcs = [d for n, _, d, _ in sp if n == "mafia.gc"]
    return {
        "flushes": {"bench": len(bench_flushes), "program": len(flushes),
                    "paired": paired},
        "flush_ms": flush_ms,
        "phase_ms": {n: med_ms([n]) for n in names},
        "dispatch_ms": med_ms(["mafia.batch.put", "mafia.batch.forward",
                               "mafia.batch.slice"]),
        "fetch_ms": med_ms(["mafia.flush.fetch"]),
        "rows_ms": med_ms(["mafia.flush.stack", "mafia.flush.scatter"]),
        "children_ms": covered_ms,
        "covered_share": (None if not flush_ms else covered_ms / flush_ms),
        "late_ms": _median([f["stats"].get("late_us", 0.0) * 1e-3
                            for f in flushes]),
        "reasons": {r: sum(f["stats"].get("reason") == r for f in flushes)
                    for r in {f["stats"].get("reason") for f in flushes}},
        "gc": {"n": len(gcs), "total_ms": sum(gcs) * 1e3,
               "max_ms": max(gcs, default=0.0) * 1e3},
        "compiles": sum(n == "mafia.compile" for n, *_ in sp),
        "idle_innermost": spans.idle_innermost(ex),
    }


def cut(ex: dict, ms: float) -> dict:
    """``ms`` of the trace from the window's middle, with window marks at
    its edges."""
    from harness import spans, trace

    lo, hi = spans.window(ex)
    a = (lo + hi) / 2
    b = a + ms * 1e6

    def keep(events):
        return [e for e in events if e[1] < b and e[1] + e[2] > a]

    return {
        "ops": {p: keep(ev) for p, ev in ex["ops"].items()},
        "modules": {p: keep(ev) for p, ev in ex["modules"].items()},
        "host": ([[trace.OPEN, a, 0], [trace.CLOSE, b, 0]]
                 + [e for e in keep(ex["host"])
                    if e[0] not in (trace.OPEN, trace.CLOSE)]),
        "spans": keep(ex["spans"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cut-ms", type=float, default=30.0)
    ap.add_argument("--cut-out")
    args = ap.parse_args(argv)
    bench_run._paths()
    from harness import cell, spans, spec, trace

    bench = spec.benchmark()
    device = bench_run.device_of(spec.cell(bench, args.workload)["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # keep the program's spans of the trace the run reduces
    kept: dict = {}
    extract = trace.extract

    def extract_with_spans(path):
        ex = extract(path)
        kept.update(ex, spans=spans.extract(path))
        return ex

    trace.extract = extract_with_spans
    metrics = spec.metrics
    spec.metrics = lambda b, c, traced: (metrics(b, c, False)
                                         + metrics(b, c, traced))
    result = cell.run(bench, args.workload, args.seed, args.seconds, True,
                      device, T_START)
    print(json.dumps(result), flush=True)
    print(json.dumps({"phases": summarize(kept)}), flush=True)
    if args.cut_out:
        part = cut(kept, args.cut_ms)
        with open(args.cut_out, "w") as f:
            json.dump(part, f)
        print(json.dumps({"cut": summarize(part)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
