"""Async multi-tenant serving tier: continuous batching over many programs.

The sync :class:`~repro.serve.classical_engine.ClassicalServeEngine` drains
its queue only when the caller says ``step()`` — fine for offline sweeps,
wrong for a server where requests arrive staggered and each carries a
latency SLO.  This module is the production tier on top of the same batched
forward:

* **Multi-tenant**: many models registered by name, each with its own
  admission queue, SLO deadline, bucket cap and batch mode.  Requests are
  routed by model name; the device is shared.
* **Continuous batching**: :meth:`poll` flushes any *full* bucket
  immediately, and flushes a *partially-empty* bucket as soon as waiting
  longer would either miss the oldest request's SLO deadline (margin = the
  model's expected batch latency) or exceed the model's ``batch_wait`` —
  so occupancy climbs above 1 under staggered arrivals without ever
  trading an unbounded wait for it.
* **Bounded admission**: each model's queue has a limit; a full queue
  rejects at ``submit`` (:class:`~repro.serve.scheduling.QueueFull`) —
  backpressure, not unbounded memory.
* **LRU residency**: at most ``max_resident`` programs keep their compiled
  callables (and jit caches) alive.  The least-recently-served model is
  evicted into the persistent artifact store
  (:class:`~repro.core.artifacts.ArtifactStore`) and transparently
  restored — a store *load* rebinds callables in milliseconds instead of
  re-running Best-PF — on its next request.
* **Metrics**: per-model and engine-wide
  :class:`~repro.serve.metrics.ServeMetrics` — enqueue→complete p50/p99,
  rps, batch occupancy, SLO misses, artifact cache hits/misses, host
  seconds by flush phase, flushes by reason and how late each came.
* **Spans**: each poll, flush and phase of a flush, and each collector
  pause while :meth:`run` serves, is a span of the JAX profiler's trace
  (:mod:`repro.core.tracing`), on the clock of the device's operations.

The scheduling core is deliberately **synchronous and clock-injectable**:
``submit`` / ``poll`` / ``flush`` take an explicit ``now`` and never sleep,
so tests drive deadlines with a fake clock and every decision is
deterministic.  The asyncio surface — ``submit_async`` / ``result`` /
``run`` — is a thin wrapper that owns the wake/sleep bookkeeping; the sync
:class:`ClassicalServeEngine` adapter drives the same core with
``flush(..., force=True)`` and no event loop at all.
"""

from __future__ import annotations

import asyncio
import gc
import time
from typing import Any, Callable

import numpy as np

from repro.core import tracing
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduling import (AdmissionQueue, InferRequest, QueueFull,
                                    bucket_for)

__all__ = ["AsyncServeEngine", "ModelState"]

_DEFAULT_BATCH_WAIT_S = 0.002   # flush horizon when no SLO is configured


class ModelState:
    """One registered model: program residency + queue + SLO + metrics."""

    def __init__(self, name: str, *, slo_s: float | None, batch_wait_s: float,
                 max_batch: int, mode: str, queue_limit: int | None,
                 loader: Callable[[], Any] | None) -> None:
        self.name = name
        self.slo_s = slo_s
        self.batch_wait_s = batch_wait_s
        self.max_batch = max_batch
        self.mode = mode
        self.queue = AdmissionQueue(queue_limit)
        self.loader = loader          # recompile path when no artifact hits
        self.program: Any | None = None
        self.batched: Any | None = None
        self.art_key: str | None = None   # content-addressed store key
        self.input_name: str = ""
        self.in_shape: tuple[int, ...] = ()
        self.output_names: tuple[str, ...] = ()
        self.metrics = ServeMetrics()
        self.finished: list[InferRequest] = []   # sync-adapter handoff
        self.last_used = 0                       # engine tick, for LRU
        # rolling estimate of one batched forward's wall time — the SLO
        # margin: flush when deadline - now <= this, or we'd miss it
        self.est_batch_s = 0.0
        # why the next flush runs and when the policy made the queue due;
        # set by ``poll``, read once by ``flush`` (a direct call: "call")
        self.flush_reason = "call"
        self.due_at: float | None = None

    @property
    def resident(self) -> bool:
        return self.batched is not None

    def bind(self, program: Any, max_batch: int, mode: str) -> None:
        """Make ``program`` the resident compiled form of this model."""
        gi = program.dfg.graph_inputs
        if len(gi) != 1:
            raise ValueError(
                f"serving engine handles single-input DFGs; got {sorted(gi)}")
        self.program = program
        self.batched = program.batch(max_batch, mode=mode)
        self.input_name = next(iter(gi))
        self.in_shape = gi[self.input_name].shape
        plan = getattr(program, "plan", None)
        self.output_names = (tuple(plan.outputs) if plan is not None
                             else tuple(program.dfg.outputs))


class AsyncServeEngine:
    """Multi-tenant continuous-batching engine (see module docstring).

    ``artifact_store`` enables both halves of the persistence story: the
    compile path publishes artifacts (cold-starts shared across processes)
    and LRU eviction parks programs there instead of discarding the
    expensive compile.  ``clock`` is injectable for deterministic tests.
    """

    def __init__(self, *, max_resident: int = 8,
                 artifact_store: Any | None = None,
                 queue_limit: int | None = None,
                 clock: Callable[[], float] = time.monotonic) -> None:
        if max_resident < 1:
            raise ValueError(f"max_resident must be >= 1, got {max_resident}")
        self.max_resident = max_resident
        self.artifact_store = artifact_store
        self.queue_limit = queue_limit
        self.clock = clock
        self.metrics = ServeMetrics()        # engine-wide aggregate
        self._models: dict[str, ModelState] = {}
        self._next_rid = 0
        self._tick = 0                       # LRU counter
        self._running = False
        self._wake: asyncio.Event | None = None
        self._gc_open: tuple[Any, float] | None = None   # span, start

    # ----------------------------------------------------------- registration
    def register_model(
        self,
        name: str,
        program: Any,
        *,
        slo_ms: float | None = None,
        batch_wait_ms: float | None = None,
        max_batch: int = 64,
        mode: str = "vmap",
        queue_limit: int | None = None,
        **compile_kw: Any,
    ) -> ModelState:
        """Register ``program`` under ``name``.

        ``program`` is a :class:`~repro.core.compiler.CompiledProgram` or a
        benchmark name resolved through
        :func:`~repro.serve.classical_engine.get_program` (compile knobs in
        ``**compile_kw``; the engine's artifact store is threaded through, so
        the compile publishes — and later cold-starts hit — the shared
        store).  ``slo_ms`` is the per-request deadline; a partially-empty
        bucket flushes early rather than miss it.  ``batch_wait_ms`` caps
        how long the oldest request waits for its bucket to fill (default:
        ``slo/4``, or 2 ms without an SLO).
        """
        from repro.core.compiler import CompiledProgram

        if name in self._models:
            raise ValueError(f"model {name!r} already registered")
        slo_s = None if slo_ms is None else slo_ms / 1e3
        if batch_wait_ms is not None:
            wait_s = batch_wait_ms / 1e3
        elif slo_s is not None:
            wait_s = slo_s / 4
        else:
            wait_s = _DEFAULT_BATCH_WAIT_S
        loader: Callable[[], Any] | None = None
        if isinstance(program, CompiledProgram):
            if compile_kw:
                raise TypeError("compile kwargs only apply when passing a "
                                "benchmark name")
            prog = program
        else:
            bench = program
            store = self.artifact_store

            def loader() -> Any:
                from repro.serve.classical_engine import get_program

                return get_program(bench, artifact_store=store, **compile_kw)

            prog = loader()
        state = ModelState(
            name, slo_s=slo_s, batch_wait_s=wait_s, max_batch=max_batch,
            mode=mode,
            queue_limit=self.queue_limit if queue_limit is None
            else queue_limit,
            loader=loader)
        self._models[name] = state
        self._make_resident(state, prog)
        return state

    @property
    def models(self) -> tuple[str, ...]:
        return tuple(self._models)

    @property
    def resident_models(self) -> tuple[str, ...]:
        return tuple(n for n, m in self._models.items() if m.resident)

    def _model(self, name: str) -> ModelState:
        try:
            return self._models[name]
        except KeyError:
            raise KeyError(f"unknown model {name!r}; registered: "
                           f"{sorted(self._models)}") from None

    # -------------------------------------------------------------- residency
    def _make_resident(self, state: ModelState, prog: Any) -> None:
        state.bind(prog, state.max_batch, state.mode)
        if self.artifact_store is not None and state.art_key is None:
            from repro.core import artifacts

            state.art_key = artifacts.program_self_key(prog)
        state.last_used = self._tick
        self._evict_over_budget(keep=state.name)

    def _evict_over_budget(self, *, keep: str) -> None:
        resident = [m for m in self._models.values() if m.resident]
        while len(resident) > self.max_resident:
            victim = min(
                (m for m in resident if m.name != keep),
                key=lambda m: m.last_used, default=None)
            if victim is None:
                return
            self.evict(victim.name)
            resident.remove(victim)

    def evict(self, name: str) -> None:
        """Drop ``name``'s compiled callables; park the program in the
        artifact store (if configured) so restoration skips Best-PF."""
        state = self._model(name)
        if not state.resident:
            return
        if (self.artifact_store is not None and state.art_key is not None
                and not self.artifact_store.contains(state.art_key)):
            self.artifact_store.save(state.art_key, state.program)
        state.program = None
        state.batched = None
        state.metrics.evictions += 1
        self.metrics.evictions += 1

    def _ensure_resident(self, state: ModelState) -> float:
        """Bring ``state``'s program back if it was evicted; returns the
        seconds that took (0 when it was resident)."""
        if state.resident:
            state.last_used = self._tick
            return 0.0
        t0 = time.perf_counter()
        sp = tracing.span(tracing.RESTORE)
        with sp:
            prog = None
            hit = False
            if self.artifact_store is not None and state.art_key is not None:
                before = self.artifact_store.hits
                prog = self.artifact_store.load(state.art_key)
                hit = self.artifact_store.hits > before
                for m in (state.metrics, self.metrics):
                    if hit:
                        m.cache_hits += 1
                    else:
                        m.cache_misses += 1
            if prog is None:
                if state.loader is None:
                    raise RuntimeError(
                        f"model {state.name!r} was evicted and has no loader "
                        f"or artifact to restore from")
                prog = state.loader()
            self._make_resident(state, prog)
            sp.set_metadata(hit=int(hit))
        for m in (state.metrics, self.metrics):
            m.restores += 1
        return time.perf_counter() - t0

    # -------------------------------------------------------------- admission
    def submit(self, model: str, x: np.ndarray, *,
               now: float | None = None) -> InferRequest:
        """Enqueue one request; raises
        :class:`~repro.serve.scheduling.QueueFull` when the model's
        admission queue is at its bound."""
        state = self._model(model)
        x = np.asarray(x, np.float32)
        if x.shape != state.in_shape:
            raise ValueError(
                f"request shape {x.shape} != program input {state.in_shape}")
        t = self.clock() if now is None else now
        req = InferRequest(
            self._next_rid, x, model=model, t_submit=t,
            deadline=None if state.slo_s is None else t + state.slo_s)
        try:
            state.queue.push(req)
        except QueueFull:
            state.metrics.rejected += 1
            self.metrics.rejected += 1
            raise
        self._next_rid += 1
        return req

    def pending(self, model: str | None = None) -> int:
        if model is not None:
            return len(self._model(model).queue)
        return sum(len(m.queue) for m in self._models.values())

    # ------------------------------------------------------------- scheduling
    def flush(self, model: str, n: int | None = None) -> list[InferRequest]:
        """Drain up to ``n`` (default: one full bucket) queued requests of
        ``model`` through one batched forward.  The device path is exactly
        the sync engine's: stack → pad to the bucket on the host → jit
        forward → one fetch of every output → scatter.

        The flush is a ``mafia.flush`` span with its phases as child spans;
        its stats are the tenant, the rows and bucket, why it ran
        (``full``/``slo``/``wait`` from :meth:`poll`, ``force`` from a
        drain, ``call`` otherwise), how long the oldest row waited and how
        late the flush came after the policy made the queue due (engine
        clock, µs)."""
        state = self._model(model)
        if not state.queue:
            return []
        reason, due_at = state.flush_reason, state.due_at
        state.flush_reason, state.due_at = "call", None
        start = self.clock()
        take = state.max_batch if n is None else n
        rows = min(len(state.queue), take)
        wait = start - state.queue.oldest().t_submit
        late = 0.0 if due_at is None else start - due_at
        with tracing.span(tracing.FLUSH, tenant=model, n=rows,
                          bucket=bucket_for(rows, state.max_batch),
                          reason=reason, wait_us=wait * 1e6,
                          late_us=late * 1e6):
            self._tick += 1
            restore = self._ensure_resident(state)
            t1 = time.perf_counter()
            with tracing.span(tracing.STACK):
                batch = state.queue.take(take)
                X = np.stack([r.x for r in batch])
            t2 = time.perf_counter()
            lane = state.batched.phase_s
            before = dict(lane)
            # numpy rows in, numpy answers out: the lane fetches them itself
            out = state.batched(**{state.input_name: X})
            t4 = time.perf_counter()
            dev = t4 - t2
            # rolling one-batch latency estimate drives the SLO flush margin
            state.est_batch_s = (dev if state.est_batch_s == 0.0
                                 else 0.5 * state.est_batch_s + 0.5 * dev)
            with tracing.span(tracing.SCATTER):
                done = self.clock()
                for i, req in enumerate(batch):
                    req.outputs = {k: v[i] for k, v in out.items()}
                    req.output_names = state.output_names
                    req.t_done = done
                    missed = req.deadline is not None and done > req.deadline
                    for m in (state.metrics, self.metrics):
                        m.record_request(done - req.t_submit,
                                         t_submit=req.t_submit, t_done=done,
                                         missed_slo=missed)
                    state.finished.append(req)
                    if req.future is not None and not req.future.done():
                        req.future.set_result(req)
                for m in (state.metrics, self.metrics):
                    m.record_batch(len(batch), dev)
                row = (len(batch), wait, late, restore, t2 - t1,
                       lane["put"] - before["put"],
                       lane["forward"] - before["forward"],
                       lane["slice"] - before["slice"],
                       lane["fetch"] - before["fetch"],
                       time.perf_counter() - t4)
                for m in (state.metrics, self.metrics):
                    m.record_flush(reason, row)
        return batch

    def poll(self, now: float | None = None, *,
             force: bool = False) -> list[InferRequest]:
        """One continuous-batching round over every model: flush each full
        bucket, plus any partial bucket whose oldest request is *due* —
        its SLO deadline within one estimated batch latency, or its
        ``batch_wait`` exhausted.  ``force`` drains everything."""
        t = self.clock() if now is None else now
        completed: list[InferRequest] = []
        with tracing.span(tracing.POLL):
            for state in self._models.values():
                q = state.queue
                while len(q) >= state.max_batch:
                    # due when the row that filled the bucket arrived
                    state.flush_reason = "full"
                    state.due_at = q[state.max_batch - 1].t_submit
                    completed.extend(self.flush(state.name))
                if not q:
                    continue
                if force:
                    state.flush_reason = "force"
                elif q.due(t, margin=state.est_batch_s,
                           max_wait=state.batch_wait_s):
                    state.flush_reason, state.due_at = q.due_moment(
                        margin=state.est_batch_s, max_wait=state.batch_wait_s)
                else:
                    continue
                completed.extend(self.flush(state.name))
        return completed

    def next_due_in(self, now: float | None = None) -> float | None:
        """Seconds until some model's queue becomes due — the run loop's
        sleep horizon.  None when every queue is empty."""
        t = self.clock() if now is None else now
        horizons = [
            m.queue.next_due_in(t, margin=m.est_batch_s,
                                max_wait=m.batch_wait_s)
            for m in self._models.values()
        ]
        horizons = [h for h in horizons if h is not None]
        return min(horizons) if horizons else None

    def drain(self) -> list[InferRequest]:
        """Synchronously run every queue dry (sync driver / shutdown path)."""
        completed: list[InferRequest] = []
        while self.pending():
            completed.extend(self.poll(force=True))
        return completed

    # ------------------------------------------------------------ async layer
    async def submit_async(self, model: str, x: np.ndarray) -> InferRequest:
        """Enqueue from a coroutine; the returned request carries a future
        resolved at completion (``await engine.result(req)``)."""
        req = self.submit(model, x)
        req.future = asyncio.get_running_loop().create_future()
        if self._wake is not None:
            self._wake.set()
        return req

    async def result(self, req: InferRequest) -> InferRequest:
        """Wait for ``req`` to complete.  Requests submitted via the sync
        path (no future) fall back to polling the ``done`` flag."""
        if req.future is not None:
            return await req.future
        while not req.done:
            await asyncio.sleep(0)
        return req

    def _gc_hook(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook: each collection a ``mafia.gc`` span and
        a count in the engine-wide metrics."""
        if phase == "start":
            sp = tracing.span(tracing.GC, generation=info["generation"])
            sp.__enter__()
            self._gc_open = (sp, time.perf_counter())
        elif self._gc_open is not None:
            sp, t0 = self._gc_open
            self._gc_open = None
            sp.__exit__(None, None, None)
            self.metrics.record_gc(info["generation"],
                                   time.perf_counter() - t0)

    async def run(self) -> None:
        """The serving loop: poll, then sleep until the next deadline
        horizon or a new submission wakes it.  Runs until :meth:`stop`.
        While it runs, collector pauses are spans and counters."""
        self._running = True
        self._wake = asyncio.Event()
        hook = self._gc_hook
        gc.callbacks.append(hook)
        try:
            while self._running:
                self.poll()
                horizon = self.next_due_in()
                try:
                    if horizon is None:           # idle: wait for a submit
                        await self._wake.wait()
                    elif horizon > 0:
                        await asyncio.wait_for(self._wake.wait(), horizon)
                    else:                         # due now — yield only
                        await asyncio.sleep(0)
                except asyncio.TimeoutError:
                    pass
                self._wake.clear()
        finally:
            self._running = False
            if self.pending():                    # never strand requests
                self.drain()
            gc.callbacks.remove(hook)

    def stop(self) -> None:
        self._running = False
        if self._wake is not None:
            self._wake.set()

    # ---------------------------------------------------------------- metrics
    def stats(self) -> dict:
        """Engine-wide + per-model metric snapshots (see
        :meth:`repro.serve.metrics.ServeMetrics.snapshot`)."""
        snap = self.metrics.snapshot()
        snap["models"] = {n: m.metrics.snapshot()
                          for n, m in self._models.items()}
        for n, m in self._models.items():
            if m.resident:    # the resident batch lane's own counters
                snap["models"][n]["batch_phase_s"] = dict(m.batched.phase_s)
                snap["models"][n]["host_pad_rows"] = m.batched.host_pad_rows
        snap["resident"] = list(self.resident_models)
        return snap
