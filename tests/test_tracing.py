"""Spans and counters of the serving path.

Spans are read back from a real profiler session on the CPU (the same
``.xplane.pb`` a chip run writes); the scheduling facts behind them
(why a flush ran, how late it came) are checked with a fake clock through
the engine's counters.  With no session the engine behaves as before: the
engine's own tests run without one.
"""

import asyncio
import gc
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tracing
from repro.launch import compile_cache
from repro.serve.async_engine import AsyncServeEngine
from repro.serve.classical_engine import get_program
from repro.serve.metrics import PHASES

from test_async_engine import BENCH, FakeClock, _requests

# the batch lane fetches every output before it slices on the host
CHILDREN = (tracing.STACK, tracing.PUT, tracing.FORWARD, tracing.FETCH,
            tracing.SLICE, tracing.SCATTER)


def _trace(tmp_path, fn):
    """Run ``fn`` under a profiler session; returns the ``mafia.*`` host
    events as (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events.extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats))
                for e in line.events if e.name.startswith("mafia."))
    return sorted(events, key=lambda e: e[1])


def _warm_engine(clock=None, **kw):
    eng = AsyncServeEngine(clock=clock or FakeClock())
    eng.register_model("m", get_program(BENCH), **kw)
    X = _requests(8)
    for x in X[:3]:
        eng.submit("m", x)
    eng.flush("m")                  # compile the bucket off the record
    return eng, X


def test_flush_spans_nest_with_stats(tmp_path):
    eng, X = _warm_engine(max_batch=4, batch_wait_ms=1e6)
    for x in X[:4]:
        eng.submit("m", x)
    events = _trace(tmp_path, eng.poll)
    names = [e[0] for e in events]
    assert names.count(tracing.POLL) == 1
    (flush,) = [e for e in events if e[0] == tracing.FLUSH]
    stats = flush[3]
    assert stats["tenant"] == "m" and stats["reason"] == "full"
    assert stats["n"] == 4 and stats["bucket"] == 4
    assert "wait_us" in stats and "late_us" in stats
    poll = next(e for e in events if e[0] == tracing.POLL)
    assert poll[1] <= flush[1] and flush[2] <= poll[2]
    for child in CHILDREN:
        (ev,) = [e for e in events if e[0] == child]
        assert flush[1] <= ev[1] and ev[2] <= flush[2], child
    starts = [next(e[1] for e in events if e[0] == c) for c in CHILDREN]
    assert starts == sorted(starts)          # the phases run in order
    assert next(e for e in events if e[0] == tracing.PUT)[3]["bucket"] == 4


def test_restore_span_and_counter(tmp_path):
    from repro.core.artifacts import ArtifactStore

    eng = AsyncServeEngine(clock=FakeClock(), max_resident=1,
                           artifact_store=ArtifactStore(tmp_path / "store"))
    eng.register_model("a", BENCH, strategy="none", batch_wait_ms=1e6)
    eng.register_model("b", "protonn/usps-b", strategy="none",
                       batch_wait_ms=1e6)
    eng.submit("a", _requests(1)[0])
    events = _trace(tmp_path / "trace", lambda: eng.flush("a"))
    (restore,) = [e for e in events if e[0] == tracing.RESTORE]
    assert restore[3]["hit"] == 1
    flush = next(e for e in events if e[0] == tracing.FLUSH)
    assert flush[1] <= restore[1] and restore[2] <= flush[2]
    snap = eng.stats()
    assert snap["restores"] == 1 and snap["models"]["a"]["restores"] == 1
    assert snap["phase_s"]["restore"] > 0


@pytest.mark.parametrize("case", ["full", "wait", "slo", "call"])
def test_reason_and_lateness_on_a_fake_clock(case):
    clock = FakeClock()
    kw = {"full": dict(max_batch=4, batch_wait_ms=1e6),
          "wait": dict(max_batch=16, batch_wait_ms=10.0),
          "slo": dict(max_batch=16, slo_ms=10.0, batch_wait_ms=1e6),
          "call": dict(max_batch=16, batch_wait_ms=1e6)}[case]
    eng, X = _warm_engine(clock, **kw)
    state = eng._models["m"]
    state.est_batch_s = 0.002
    count = state.metrics.flushes_by_reason.get(case, 0)
    clock.t = 1.0
    for i in range(4 if case == "full" else 2):
        clock.t = 1.0 + 0.001 * i
        eng.submit("m", X[i])
    # full: due when the 4th row came (1.003); wait: 1.0 + 10 ms;
    # slo: deadline 1.010 less the 2 ms margin
    clock.t, due = {"full": (1.005, 1.003), "wait": (1.013, 1.010),
                    "slo": (1.0085, 1.008), "call": (1.004, None)}[case]
    done = eng.flush("m") if case == "call" else eng.poll()
    assert len(done) == (4 if case == "full" else 2)
    rec = state.metrics.recent_flushes()
    assert state.metrics.flushes_by_reason[case] == count + 1
    late = 0.0 if due is None else clock.t - due
    assert rec["late_s"][-1] == pytest.approx(late, abs=1e-9)
    assert rec["wait_s"][-1] == pytest.approx(clock.t - 1.0, abs=1e-9)
    assert rec["n"][-1] == len(done)
    assert eng.stats()["late_s"] == pytest.approx(late, abs=1e-9)
    assert state.flush_reason == "call" and state.due_at is None


def test_drain_reads_force():
    eng, X = _warm_engine(max_batch=16, batch_wait_ms=1e6)
    eng.submit("m", X[0])
    eng.drain()
    assert eng.metrics.flushes_by_reason["force"] == 1
    assert eng.metrics.recent_flushes()["late_s"][-1] == 0.0


def test_phases_sum_within_the_flush_wall_time():
    eng, X = _warm_engine(time.monotonic, max_batch=16, batch_wait_ms=1e6)
    before = dict(eng.metrics.phase_s)
    lane = dict(eng._models["m"].batched.phase_s)
    for x in X:
        eng.submit("m", x)
    t0 = time.perf_counter()
    eng.flush("m")
    wall = time.perf_counter() - t0
    phases = {k: eng.metrics.phase_s[k] - before[k] for k in PHASES}
    assert all(v >= 0 for v in phases.values())
    assert 0 < sum(phases.values()) <= wall
    row = eng.metrics.recent_flushes()
    assert sum(row[k][-1] for k in PHASES) == pytest.approx(
        sum(phases.values()))
    # the batch lane's own counters agree with the engine's phases
    now = eng._models["m"].batched.phase_s
    for k in ("put", "forward", "fetch", "slice"):
        assert now[k] - lane[k] == pytest.approx(phases[k])
    stats = eng.stats()["models"]["m"]
    assert set(stats["batch_phase_s"]) == {"put", "forward", "fetch",
                                           "slice"}
    assert set(stats["phase_s"]) == set(PHASES)


def test_batched_program_phases_over_chunks():
    bp = get_program(BENCH).batch(max_batch=8)
    out = bp(x=_requests(11))               # chunks of 8 and 3
    assert out["ClassSum"].shape[0] == 11
    assert bp.stats == {8: 1, 4: 1}
    assert all(v > 0 for v in bp.phase_s.values())
    assert bp.fn.__name__ == "mafia_forward_vmap"


def test_compile_counts_and_cache_loads(tmp_path):
    """A new jit adds a program; after ``jax.clear_caches()`` the same
    program is loaded from the persistent cache: a cache hit, marked."""
    from jax.experimental.compilation_cache import compilation_cache

    compile_cache.count_compiles()
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes,
           jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        f = jax.jit(lambda x: x * 3.0 - 1.5)
        x = jnp.arange(5.0)
        c0 = compile_cache.counts()
        f(x).block_until_ready()
        c1 = compile_cache.counts()
        assert c1["programs"] > c0["programs"]
        assert c1["compile_s"] > c0["compile_s"]
        jax.clear_caches()
        events = _trace(tmp_path / "trace",
                        lambda: f(x).block_until_ready())
        c2 = compile_cache.counts()
        assert c2["cache_hits"] > c1["cache_hits"]
        marks = [e for e in events if e[0] == tracing.COMPILE]
        assert any(e[3]["hit"] == 1 and "lambda" in e[3]["fun"]
                   for e in marks)
    finally:
        (d, t, b, on) = old
        jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", t)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", b)
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()


def test_gc_pauses_are_spans_while_run_serves(tmp_path):
    eng = AsyncServeEngine()

    async def serve():
        runner = asyncio.create_task(eng.run())
        await asyncio.sleep(0)               # the loop is running
        assert eng._gc_hook in gc.callbacks
        gc.collect(2)
        eng.stop()
        await runner

    events = _trace(tmp_path, lambda: asyncio.run(serve()))
    assert any(e[0] == tracing.GC and e[3]["generation"] == 2
               for e in events)
    assert eng._gc_hook not in gc.callbacks
    snap = eng.stats()
    assert snap["gc_collections"].get(2, 0) >= 1 and snap["gc_s"] > 0
    n = snap["gc_collections"][2]
    gc.collect(2)                             # no hook after stop()
    assert eng.stats()["gc_collections"][2] == n


def test_spans_cost_little_without_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.span(tracing.FLUSH, tenant="m", n=1):
            pass
    assert (time.perf_counter() - t0) / n < 50e-6
