"""The open-loop sender every open-loop traffic kind shares.

Requests are sent on a schedule whether or not earlier ones have finished;
each is timed from when it was due.  The generator runs on the engine's
event loop: it wakes, sends every request whose time has come, and sleeps
until the next one is due, so a late wake shows as latency.
"""

from __future__ import annotations

import asyncio

import numpy as np


async def drive(ctx, due: np.ndarray, tenant: np.ndarray) -> None:
    """Send request ``i`` to tenant ``tenant[i]`` at ``ctx.t0 + due[i]``;
    return once every request sent has its answer (or ``ctx.grace_s``
    after the window closes)."""
    log = ctx.log
    log.open(due, tenant, ctx.rows_for(tenant))
    clients = []
    i, n = 0, len(due)
    clock, t0 = ctx.clock, ctx.t0
    while i < n:
        now = clock()
        j = i
        with ctx.span("bench.submit"):
            while j < n and t0 + due[j] <= now:
                req = await ctx.engine.submit_async(
                    ctx.names[tenant[j]], ctx.row(j))
                log.t_submit[j] = clock()
                clients.append(asyncio.create_task(_client(ctx, j, req)))
                j += 1
        i = j
        if i < n:
            await asyncio.sleep(max(0.0, t0 + due[i] - clock()))
    await ctx.wait_all(clients)


async def _client(ctx, i: int, req) -> None:
    req = await ctx.engine.result(req)
    with ctx.span("bench.result"):
        ctx.log.done(i, req)
