"""Open loop, Poisson arrivals at a steady mean rate: the number of
requests the rate gives over the window, at uniform random times, so every
seed sends the same amount of work in another order."""

from __future__ import annotations

import numpy as np

from loadgen import open_loop, popularity


def schedule(traffic: dict, n_tenants: int, seconds: float,
             rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """(due times in seconds from the window's start, tenant index)."""
    rate = float(traffic["rate_rps"])
    n = int(round(rate * seconds))
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    return due, popularity.assign(due, n_tenants, traffic["popularity"], rng)


async def drive(ctx, traffic: dict) -> None:
    due, tenant = schedule(traffic, len(ctx.names), ctx.seconds, ctx.rng)
    await open_loop.drive(ctx, due, tenant)
