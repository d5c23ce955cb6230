"""The reduction from trace to device numbers."""

from __future__ import annotations

import pytest

from harness import trace

MS = 1_000_000  # ns


def _hand_trace():
    # window [10, 110) ms; device ops overlap at 20-30 and 25-40, then
    # 60-70 and one op straddling the close; host flush spans cover part
    # of the idle time.
    return {
        "ops": {"/device:TPU:0": [
            ["fusion.1", 20 * MS, 10 * MS],
            ["custom-call.2", 25 * MS, 15 * MS],
            ["fusion.1", 60 * MS, 10 * MS],
            ["copy.3", 105 * MS, 20 * MS],
            ["early", 0, 5 * MS]]},
        "modules": {"/device:TPU:0": [
            ["jit__lambda(7)", 20 * MS, 20 * MS],
            ["jit_pad(3)", 60 * MS, 10 * MS]]},
        "host": [
            [trace.OPEN, 10 * MS, 0],
            [trace.CLOSE, 110 * MS, 0],
            ["bench.flush", 15 * MS, 10 * MS],      # 5 ms of it idle
            ["bench.flush", 45 * MS, 10 * MS],      # all idle
            ["bench.submit", 80 * MS, 5 * MS]],     # all idle
    }


def test_busy_idle_and_ops():
    s = trace.reduce(_hand_trace())
    assert s.window_s == pytest.approx(0.100)
    # union: 20-40, 60-70, 105-110 = 35 ms
    assert s.busy_s == pytest.approx(0.035)
    assert s.idle_share == pytest.approx(0.65)
    assert s.top_ops[0] == ("fusion.1", pytest.approx(0.020))
    assert dict(s.top_ops)["custom-call.2"] == pytest.approx(0.015)
    # the op straddling the close counts up to the close only
    assert dict(s.top_ops)["copy.3"] == pytest.approx(0.005)
    assert "early" not in dict(s.top_ops)


def test_idle_by_host_span():
    idle = dict(trace.reduce(_hand_trace()).idle_by_host)
    assert idle["bench.flush"] == pytest.approx(0.015)
    assert idle["bench.submit"] == pytest.approx(0.005)
    assert idle[trace.NO_SPAN] == pytest.approx(0.065 - 0.020)
    assert sum(idle.values()) == pytest.approx(0.065)


def test_forward_device_time():
    s = trace.reduce(_hand_trace())
    assert s.device_s("jit__lambda") == pytest.approx(0.020)
    assert s.device_s("jit_pad") == pytest.approx(0.010)


def test_op_names():
    assert trace.op_name(
        "%launch.1 = (f32[64,1,2]{2,1,0:T(1,128)S(1)}, s32[64,1,1]{2,1,0}) "
        "custom-call(f32[64,1,1000]{2,1,0:T(1,128)S(1)} %reshape.0)"
    ) == "launch.1 custom-call"
    assert trace.op_name("%copy.1 = f32[64,400]{1,0:T(8,128)} copy("
                         "f32[64,400]{1,0:T(8,128)} %array.1)") == "copy.1 copy"
    assert trace.op_name("fusion.1") == "fusion.1"


def test_no_marks_or_no_device_is_an_error():
    t = _hand_trace()
    t["host"] = t["host"][2:]
    with pytest.raises(ValueError):
        trace.reduce(t)
    t = _hand_trace()
    t["ops"] = {}
    with pytest.raises(ValueError):
        trace.reduce(t)


def test_recorded_trace_against_a_time_grid():
    """A 30 ms cut of a traced run of ``protonn-t1-f32.full`` on one v5e,
    reduced, and checked against a brute-force count on a 10 ns grid."""
    import numpy as np

    from conftest import DATA

    ex = trace.load(DATA / "trace_protonn_full_30ms.json")
    s = trace.reduce(ex)
    marks = {n: t for n, t, _ in ex["host"]}
    lo, hi = marks[trace.OPEN], marks[trace.CLOSE]
    grid = np.arange(lo, hi, 10.0)

    def covered(events):
        depth = np.zeros(len(grid) + 1, np.int64)
        for _, a, d in events:
            depth[np.searchsorted(grid, a)] += 1
            depth[np.searchsorted(grid, a + d)] -= 1
        return np.cumsum(depth)[:-1] > 0

    (ops,) = ex["ops"].values()
    busy = covered(ops)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert s.busy_s == pytest.approx(busy.mean() * s.window_s, abs=1e-6)
    idle = dict(s.idle_by_host)
    for name in ("bench.flush", "bench.submit", "bench.result"):
        span = covered([e for e in ex["host"] if e[0] == name])
        assert idle[name] == pytest.approx(
            np.mean(span & ~busy) * s.window_s, abs=1e-6)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    # the megakernel launch is the op that takes most device time
    assert s.top_ops[0][0] == "launch.1 custom-call"
    fwd = s.device_s("jit__lambda")
    assert 0 < fwd <= s.busy_s
