"""Closed loop: each tenant keeps ``outstanding`` requests in flight; a
client sends its next request as soon as its answer arrives.  Clients
start ``preroll_s`` before the window so that it opens on a steady state;
they stop sending when it closes.  Each client walks the tenant's rows from
a seeded offset."""

from __future__ import annotations

import asyncio


async def drive(ctx, traffic: dict) -> None:
    per = int(traffic["outstanding"])
    ctx.log.closed()
    start = ctx.t0 - float(traffic["preroll_s"])
    await asyncio.sleep(max(0.0, start - ctx.clock()))
    pool = len(ctx.pool[0])
    offsets = ctx.rng.integers(0, pool, size=(len(ctx.names), per))
    clients = [asyncio.create_task(_client(ctx, k, int(off)))
               for k in range(len(ctx.names)) for off in offsets[k]]
    await ctx.wait_all(clients)


async def _client(ctx, k: int, offset: int) -> None:
    """One client of tenant ``k``: sends the tenant's rows in turn from
    ``offset``, so its inputs depend on the seed alone."""
    clock, end, name = ctx.clock, ctx.t0 + ctx.seconds, ctx.names[k]
    rows = ctx.pool[k]
    row = offset
    while clock() < end:
        row = (row + 1) % len(rows)
        with ctx.span("bench.submit"):
            i = ctx.log.closed_sent(k, row, clock())
            req = await ctx.engine.submit_async(name, rows[row])
        req = await ctx.engine.result(req)
        with ctx.span("bench.result"):
            ctx.log.closed_done(i, req)
