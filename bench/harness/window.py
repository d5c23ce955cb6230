"""The measured window: the engine as users run it, the traffic, the records.

Requests enter through ``submit_async`` / ``result`` and the engine's own
``run()`` loop; the load generator runs on the same event loop.  The
benchmark's engine subclass only times the public ``flush``: it changes
nothing of what the engine does.
"""

from __future__ import annotations

import asyncio
import contextlib

import numpy as np

from harness.session import CLOCK, span
from repro.serve.async_engine import AsyncServeEngine


class BenchEngine(AsyncServeEngine):
    """``AsyncServeEngine`` with a span around every ``flush``: its start and
    end, the tenant, how many requests it carried and in which bucket."""

    def __init__(self, **kw) -> None:
        super().__init__(clock=CLOCK, **kw)
        self.recording = False
        self.flushes: list[tuple[str, int, int, float, float]] = []

    def flush(self, model, n=None):
        t0 = CLOCK()
        with span("bench.flush"):
            batch = super().flush(model, n)
        t1 = CLOCK()
        if batch:
            for req in batch:
                req.bench_flush_t0 = t0
            if self.recording:
                bucket = self._model(model).batched.bucket(len(batch))
                self.flushes.append((model, len(batch), bucket, t0, t1))
        return batch


class Log:
    """Per-request records: due, sent, answered, the flush that carried it,
    and the answer as the client received it."""

    def __init__(self, clock=CLOCK) -> None:
        self.clock = clock
        self.open_loop = True

    def open(self, due: np.ndarray, tenant: np.ndarray,
             row: np.ndarray) -> None:
        n = len(due)
        self.due_rel = np.asarray(due, np.float64)
        self.tenant = np.asarray(tenant, np.int64)
        self.row = np.asarray(row, np.int64)
        self.t_submit = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.t_flush = np.full(n, np.nan)
        self.outputs: list = [None] * n

    def done(self, i: int, req) -> None:
        self.t_done[i] = self.clock()
        self.t_flush[i] = req.bench_flush_t0
        self.outputs[i] = req.outputs

    def closed(self) -> None:
        self.open_loop = False
        self._rows: list[list] = []

    def closed_sent(self, k: int, row: int, t_sent: float) -> int:
        self._rows.append([k, row, t_sent, np.nan, np.nan, None])
        return len(self._rows) - 1

    def closed_done(self, i: int, req) -> None:
        self._rows[i][3:] = [self.clock(), req.bench_flush_t0, req.outputs]

    def finish(self, t0: float) -> None:
        """Make the closed loop's records arrays like the open loop's; due
        times become seconds from the window's start in both."""
        if self.open_loop:
            self.due = self.due_rel
        else:
            rows = self._rows
            self.tenant = np.array([r[0] for r in rows], np.int64)
            self.row = np.array([r[1] for r in rows], np.int64)
            self.t_submit = np.array([r[2] for r in rows])
            self.t_done = np.array([r[3] for r in rows])
            self.t_flush = np.array([r[4] for r in rows])
            self.outputs = [r[5] for r in rows]
            self.due = self.t_submit - t0
        for name in ("t_submit", "t_done", "t_flush"):
            setattr(self, name, getattr(self, name) - t0)


class Ctx:
    """What a traffic kind sees: the engine, the tenants' names and rows,
    the window, the seeded generator and the log."""

    def __init__(self, engine, names, pool, t0, seconds, rng, grace_s,
                 traced: bool) -> None:
        self.engine, self.names, self.pool = engine, names, pool
        self.t0, self.seconds, self.rng = t0, seconds, rng
        self.grace_s = grace_s
        self.clock = CLOCK
        self.log = Log()
        self._traced = traced
        self._rows_of: np.ndarray | None = None

    def span(self, name: str):
        return span(name) if self._traced else contextlib.nullcontext()

    def rows_for(self, tenant: np.ndarray) -> np.ndarray:
        """A seeded row of its tenant's pool for every request."""
        sizes = np.array([len(p) for p in self.pool])
        self._rows_of = (self.rng.random(len(tenant))
                         * sizes[tenant]).astype(np.int64)
        return self._rows_of

    def row(self, i: int) -> np.ndarray:
        return self.pool[self.log.tenant[i]][self._rows_of[i]]

    async def wait_all(self, tasks: list) -> None:
        """Wait for the clients, at most ``grace_s`` past the window's close;
        a client still waiting then has its request counted unanswered."""
        if not tasks:
            return
        left = self.t0 + self.seconds + self.grace_s - self.clock()
        _, pending = await asyncio.wait(tasks, timeout=max(0.0, left))
        for t in pending:
            t.cancel()
        results = await asyncio.gather(*tasks, return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException) and not isinstance(
                    r, asyncio.CancelledError):
                raise r


async def serve(ctx, drive, traffic: dict, sess) -> None:
    """Run the engine's loop and the traffic until every client is done;
    ``sess`` (a ``session.Session``) marks the window in a traced run."""
    engine = ctx.engine
    runner = asyncio.create_task(engine.run())
    marks = (asyncio.create_task(sess.marks(ctx.t0, ctx.seconds))
             if sess.traced else None)
    engine.recording = True
    try:
        await drive(ctx, traffic)
    finally:
        engine.recording = False
        engine.stop()
        await runner
        if marks is not None:
            await marks
