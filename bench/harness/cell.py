"""One run of one cell: the serving path the configuration names, the
check, the metrics.  What belongs to a path lives in ``paths/<name>.py``."""

from __future__ import annotations

import sys

from harness import check, spec


def memory_peak_bytes(count: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()[:count]]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def run(bench: dict, cell_name: str, seed: int, seconds: float,
        traced: bool, device: dict, t_start: float, faults=None) -> dict:
    """Run the cell and return the result line (a dict).  ``faults``, for
    the benchmark's own tests, breaks the served path after set-up."""
    cell = spec.cell(bench, cell_name)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    path = spec.path(cfg)
    su = path.set_up(cfg, device)
    pool = path.rows(su, seed)
    if faults is not None:
        faults(su)
    r = path.measure(su, cell, traffic, pool, seed, seconds, traced, t_start)
    device = dict(device, memory_peak_bytes=memory_peak_bytes(
        device["count"]))

    nums, attempted, failed = path.numbers(r, pool)
    ok, shown = check.verdict(nums, cfg["limits"])
    metrics = {}
    for m in spec.metrics(bench, cell_name, traced):
        v = spec.reader(m["name"])(r)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if r.trace is not None:
        device["busy_s"] = r.trace.busy_s
        device["window_s"] = r.trace.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in r.trace.top_ops],
            "idle_gaps": [[n, s] for n, s in r.trace.idle_by_host]}
    result["checks"] = shown
    for k, v in shown.items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    return result
