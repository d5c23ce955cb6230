"""The program's spans and flush records, as the benchmark reads them."""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np
import pytest

from harness import spans, spec, trace

MS = 1_000_000  # ns
NEW_READERS = ("flush_dispatch_ms.full", "flush_dispatch_ms.open",
               "flush_fetch_ms.full", "flush_fetch_ms.open",
               "flush_rows_ms.full", "flush_late_ms")


def _hand_trace():
    # window [0, 100) ms; the device is busy 20-30 ms, inside the fetch of
    # one flush; the program's spans nest inside the benchmark's flush.
    return {
        "ops": {"/device:TPU:0": [["mafia_grid_seg0.1", 20 * MS, 10 * MS]]},
        "modules": {"/device:TPU:0": [["jit_mafia_forward_vmap(3)",
                                       20 * MS, 10 * MS]]},
        "host": [
            [trace.OPEN, 0, 0],
            [trace.CLOSE, 100 * MS, 0],
            ["bench.flush", 10 * MS, 40 * MS]],
        "spans": [
            ["mafia.flush", 12 * MS, 36 * MS,
             {"tenant": "a", "n": 3, "reason": "wait", "late_us": 1500.0}],
            ["mafia.flush.stack", 13 * MS, 2 * MS, {}],
            ["mafia.batch.put", 16 * MS, 2 * MS, {"bucket": 4}],
            ["mafia.batch.forward", 18 * MS, 1 * MS, {"bucket": 4}],
            ["mafia.batch.slice", 19 * MS, 1 * MS, {}],
            ["mafia.flush.fetch", 20 * MS, 20 * MS, {}],
            ["mafia.flush.scatter", 41 * MS, 6 * MS, {}],
            ["mafia.compile", 70 * MS, 0, {"fun": "jit(_pad)", "hit": 1}],
            ["mafia.flush", 150 * MS, 1 * MS, {}]],     # after the close
    }


def test_idle_goes_to_the_innermost_span():
    idle = dict(spans.idle_innermost(_hand_trace()))
    want = {spans.NO_SPAN: 60, "bench.flush": 4, "mafia.flush": 4,
            "mafia.flush.stack": 2, "mafia.batch.put": 2,
            "mafia.batch.forward": 1, "mafia.batch.slice": 1,
            "mafia.flush.fetch": 10, "mafia.flush.scatter": 6}
    assert set(idle) == set(want)
    for k, ms in want.items():
        assert idle[k] == pytest.approx(ms * 1e-3), k
    # the same idle time as the benchmark's own reduction finds
    assert sum(idle.values()) == pytest.approx(
        trace.reduce(_hand_trace()).window_s
        - trace.reduce(_hand_trace()).busy_s)


def test_existing_reduction_reads_as_before():
    """The program's spans change nothing the benchmark's reduction reads:
    ``bench.flush`` still holds all idle time under it there."""
    s = trace.reduce(_hand_trace())
    assert s.busy_s == pytest.approx(0.010)
    assert dict(s.idle_by_host)["bench.flush"] == pytest.approx(0.030)
    assert s.device_s("jit_mafia_forward") == pytest.approx(0.010)


def test_children_go_to_their_flush():
    sp = spans.in_window(_hand_trace())
    assert [n for n, *_ in sp].count("mafia.flush") == 1   # in the window
    (f,) = spans.by_flush(sp)
    assert f["dur"] == pytest.approx(0.036)
    assert f["stats"]["reason"] == "wait"
    assert f["children"] == pytest.approx({
        "mafia.flush.stack": 0.002, "mafia.batch.put": 0.002,
        "mafia.batch.forward": 0.001, "mafia.batch.slice": 0.001,
        "mafia.flush.fetch": 0.020, "mafia.flush.scatter": 0.006})


def _record(metrics, n, late, phases):
    """One flush record: ``phases`` in order restore, stack, put, forward,
    slice, fetch, scatter."""
    metrics.record_flush("wait", (n, 0.0, late) + tuple(phases))


def _hand_run(seconds=1.0):
    """Two tenants: three set-up flushes each, then the window's flushes
    (one before the window opens), as the benchmark records them."""
    from repro.serve.metrics import ServeMetrics

    tenants = [SimpleNamespace(state=SimpleNamespace(metrics=ServeMetrics()))
               for _ in range(2)]
    for t in tenants:
        for n in (1, 2, 3):
            _record(t.state.metrics, n, 0.0, [0.0, 9, 9, 9, 9, 9, 9])
    fl = {"tenant": [], "n": [], "t0": []}
    # tenant 0: flushes at -0.1 (before the window), 0.2, 0.4;
    # tenant 1: 0.3, 0.5
    for k, n, t0, late, ph in [
            (0, 4, -0.1, 0.5, [0, 9, 9, 9, 9, 9, 9]),
            (0, 5, 0.2, 0.001, [0, 0.001, 0.001, 0.002, 0.001, 0.004, 0.002]),
            (1, 6, 0.3, 0.003, [0, 0.002, 0.001, 0.001, 0.001, 0.006, 0.001]),
            (0, 7, 0.4, 0.002, [0, 0.001, 0.002, 0.002, 0.002, 0.005, 0.003]),
            (1, 8, 0.5, 0.004, [0, 0.003, 0.001, 0.003, 0.001, 0.003, 0.004])]:
        _record(tenants[k].state.metrics, n, late, ph)
        fl["tenant"].append(k)
        fl["n"].append(n)
        fl["t0"].append(t0)
    return SimpleNamespace(
        tenants=tenants, seconds=seconds,
        flushes={k: np.asarray(v) for k, v in fl.items()},
        in_window=lambda t: (t >= 0.0) & (t <= seconds))


def test_new_readers_on_a_hand_run(monkeypatch):
    run = _hand_run()
    # window flushes: dispatch (put+forward+slice) 4, 3, 6, 5 ms -> 4 ms
    # (nearest rank); fetch 4, 6, 5, 3 -> 4; rows (stack+scatter) 3, 3, 4,
    # 7 -> 3; late 1, 3, 2, 4 -> 2
    want = {"flush_dispatch_ms.full": 4.0, "flush_dispatch_ms.open": 4.0,
            "flush_fetch_ms.full": 4.0, "flush_fetch_ms.open": 4.0,
            "flush_rows_ms.full": 3.0, "flush_late_ms": 2.0}
    for name, v in want.items():
        assert spec.reader(name)(run) == pytest.approx(v), name
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "counts",
                        lambda: {"programs": 812, "cache_hits": 800,
                                 "compile_s": 3.0})
    assert spec.reader("xla_programs")(run) == 812


def test_new_readers_without_program_records():
    """A program that keeps no flush records (the parent of this change)
    reads None, and does not raise."""
    run = _hand_run()
    for t in run.tenants:
        t.state.metrics = SimpleNamespace()
    for name in NEW_READERS:
        assert spec.reader(name)(run) is None, name


def test_records_that_do_not_match_read_none():
    run = _hand_run()
    run.flushes["n"] = run.flushes["n"] + 100
    for name in NEW_READERS:
        assert spec.reader(name)(run) is None, name


def test_records_match_a_real_window(store_in_tmp):
    """On a whole run at test size, the engine's records line up with the
    benchmark's flushes one for one, and every new reader reads."""
    from conftest import CPU_AS_V5E, tiny_bench
    from harness import flush_records
    from paths import fleet

    bench, name = tiny_bench("tiny-protonn-f32", "full")
    c = spec.cell(bench, name)
    su = fleet.set_up(spec.config(bench, c["config"]), CPU_AS_V5E)
    pool = fleet.rows(su, 2**31 + 77)
    r = fleet.measure(su, c, spec.traffic("full"), pool, 2**31 + 77, 0.5,
                      False, time.perf_counter())
    w = flush_records.window(r)
    m = r.in_window(r.flushes["t0"])
    assert np.sum(m) > 0
    assert np.array_equal(np.sort(w["n"]), np.sort(r.flushes["n"][m]))
    for reader in NEW_READERS:
        v = spec.reader(reader)(r)
        assert v is not None and v >= 0, reader
    # the phases fit inside the benchmark's own timing of each flush
    phases = ("stack", "put", "forward", "slice", "fetch", "scatter")
    total = sum(w[p] for p in phases)
    assert np.median(total) <= np.median(r.flushes["t1"][m]
                                         - r.flushes["t0"][m])


def test_phases_tool_reads_a_trace():
    """``phases.py``'s reading of the hand trace: one flush of each kind,
    paired; its phases cover 32 of its 36 ms; the cut keeps marks."""
    import phases

    s = phases.summarize(_hand_trace())
    assert s["flushes"] == {"bench": 1, "program": 1, "paired": 1}
    assert s["flush_ms"] == pytest.approx(36.0)
    assert s["dispatch_ms"] == pytest.approx(4.0)
    assert s["fetch_ms"] == pytest.approx(20.0)
    assert s["rows_ms"] == pytest.approx(8.0)
    assert s["covered_share"] == pytest.approx(32 / 36)
    assert s["late_ms"] == pytest.approx(1.5)
    assert s["reasons"] == {"wait": 1}
    assert s["compiles"] == 1 and s["gc"]["n"] == 0
    part = phases.cut(_hand_trace(), 30.0)      # 50-80 ms
    lo, hi = spans.window(part)
    assert (hi - lo) == 30 * MS
    assert [e[0] for e in part["spans"]] == ["mafia.compile"]


def test_recorded_chip_trace_with_program_spans():
    """A 30 ms cut of a traced run of ``protonn-t1-f32.full`` on one v5e
    (``phases.py --cut-out``), read as the chip run printed it: eight
    flushes paired one for one, their phases covering 98% of a flush, and
    the benchmark's own reduction reading the cut as it reads any trace."""
    import phases

    from conftest import DATA

    ex = trace.load(DATA / "trace_protonn_full_spans_30ms.json")
    s = phases.summarize(ex)
    assert s["flushes"] == {"bench": 8, "program": 8, "paired": 8}
    assert s["reasons"] == {"full": 8}
    for key, want in {"flush_ms": 2.94688, "dispatch_ms": 1.29223,
                      "fetch_ms": 1.12951, "rows_ms": 0.37892,
                      "covered_share": 0.9794155174286024,
                      "late_ms": 49.8342490000141}.items():
        assert s[key] == pytest.approx(want), key
    assert s["gc"]["n"] == 2 and s["compiles"] == 0
    idle = dict(spans.idle_innermost(ex, top=100))
    assert idle["mafia.flush.fetch"] == pytest.approx(0.010357775999999999)
    assert idle["bench.flush"] == pytest.approx(7.8828e-05)
    r = trace.reduce(ex)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    assert dict(r.idle_by_host)["bench.flush"] == pytest.approx(0.025531181)
    assert r.top_ops[0][0] == "mafia_grid_seg0.1 custom-call"
    assert r.device_s("jit_mafia_forward_vmap") == pytest.approx(
        3.2174e-4)
