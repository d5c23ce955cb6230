"""Open loop, bursty: a cycle of an ``on`` phase at ``on_factor`` times the
mean rate and an ``off`` phase at ``off_factor`` times it, Poisson within
each phase.  The factors are chosen so that the cycle's mean is the mean
rate."""

from __future__ import annotations

import numpy as np

from loadgen import open_loop, popularity


def schedule(traffic: dict, n_tenants: int, seconds: float,
             rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """(due times in seconds from the window's start, tenant index)."""
    rate = float(traffic["rate_rps"])
    cycle, on = float(traffic["cycle_s"]), float(traffic["on_s"])
    f_on, f_off = float(traffic["on_factor"]), float(traffic["off_factor"])
    mean = (on * f_on + (cycle - on) * f_off) / cycle
    if abs(mean - 1.0) > 1e-9:
        raise ValueError(f"on/off factors average {mean}, not 1")
    parts = []
    start = 0.0
    while start < seconds:
        for lo, hi, f in ((start, start + on, f_on),
                          (start + on, start + cycle, f_off)):
            hi = min(hi, seconds)
            if hi > lo:
                k = int(round(rate * f * (hi - lo)))
                parts.append(rng.uniform(lo, hi, size=k))
        start += cycle
    due = np.sort(np.concatenate(parts))
    return due, popularity.assign(due, n_tenants, traffic["popularity"], rng)


async def drive(ctx, traffic: dict) -> None:
    due, tenant = schedule(traffic, len(ctx.names), ctx.seconds, ctx.rng)
    await open_loop.drive(ctx, due, tenant)
