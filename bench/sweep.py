#!/usr/bin/env python3
"""Find the rate of an open-loop cell: serve its traffic at several mean
rates, one after another on one set-up, and print what each rate gave.

    python3 bench/sweep.py --workload <cell> --rates 500,1000,2000 --seconds 10

For each rate: latency, how late the generator sent (``send_lag``), how
long requests queued before their flush (``queue_wait``), the buckets'
fill, the flush time, full collections of the garbage collector, and the
99th percentile of the window's first and last quarter (a backlog that
grows shows as the last above the first).  Runs on the chip only, as
``run.py`` does.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench_run._paths()
    import numpy as np

    from harness import spec, stats
    from harness.stats import latency_ms, quantile

    bench = spec.benchmark()
    c = spec.cell(bench, args.workload)
    device = bench_run.device_of(c["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = spec.config(bench, c["config"])
    path = spec.path(cfg)
    su = path.set_up(cfg, device)
    slo = max(t["slo_ms"] for t in cfg["tenants"])
    pauses: list[float] = []
    started: list[float] = []

    def gc_timer(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                started.append(time.perf_counter())
            elif started:
                pauses.append(time.perf_counter() - started.pop())

    gc.callbacks.append(gc_timer)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(spec.traffic(c["traffic"]), rate_rps=rate)
        pool = path.rows(su, args.seed + k)
        pauses.clear()
        r = path.measure(su, c, traffic, pool, args.seed + k, args.seconds,
                         False, T_START)
        fl_ms = (r.flushes["t1"] - r.flushes["t0"]) * 1e3
        lat = latency_ms(r)
        due = r.log.due[r.in_window(r.log.due)]
        q = args.seconds / 4
        first = lat[due < q]
        last = lat[due >= 3 * q]
        m = r.in_window(r.log.due)
        lag = (r.log.t_submit[m] - r.log.due[m]) * 1e3
        wait = (r.log.t_flush[m] - r.log.due[m]) * 1e3
        print(json.dumps({
            "rate_rps": rate, "sent": int(len(lat)),
            "served_rps": float(np.sum(r.in_window(r.log.t_done))
                                / args.seconds),
            "backlog_at_close": int(np.sum(
                r.in_window(r.log.due) & ~(r.log.t_done <= args.seconds))),
            "p50_ms": quantile(lat, 0.5), "p99_ms": quantile(lat, 0.99),
            "p99_first_quarter_ms": quantile(first, 0.99),
            "p99_last_quarter_ms": quantile(last, 0.99),
            "max_slo_ms": slo,
            "bucket_fill_pct": spec.reader("bucket_fill_pct")(r),
            "flush_ms": spec.reader("flush_ms.open")(r),
            "flushes_per_s": float(np.sum(stats.window_flushes(r))
                                   / args.seconds),
            "per_flush": float(np.mean(r.flushes["n"][
                stats.window_flushes(r)])),
            "send_lag_p50_ms": quantile(lag, 0.5),
            "send_lag_p99_ms": spec.reader("send_lag_ms")(r),
            "queue_wait_p50_ms": spec.reader("queue_wait_ms")(r),
            "queue_wait_p99_ms": quantile(wait, 0.99),
            "batch_wait_ms": [t["slo_ms"] / 4 for t in cfg["tenants"]],
            "flushes_over_20ms": int(np.sum(fl_ms > 20)),
            "flush_max_ms": float(fl_ms.max()),
            "gc_full_collections": len(pauses),
            "gc_full_max_ms": 1e3 * max(pauses, default=0.0),
            "gc_full_total_ms": 1e3 * sum(pauses)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
