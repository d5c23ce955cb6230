"""Traffic: the same seed gives the same requests; rates, bursts and
popularity are as the mix says."""

from __future__ import annotations

import numpy as np

from loadgen import open_onoff, open_poisson, popularity

BURSTY = {"rate_rps": 4000, "cycle_s": 1.0, "on_s": 0.2, "on_factor": 3.0,
          "off_factor": 0.5,
          "popularity": {"kind": "zipf", "s": 1.0, "rotate_s": 5.0}}
STEADY = {"rate_rps": 4000, "popularity": {"kind": "uniform"}}
SEED = 2**31 + 77          # wider than 32 signed bits


def _sched(mod, traffic, seed, seconds=20.0):
    return mod.schedule(traffic, 10, seconds, np.random.default_rng([seed, 2]))


def test_deterministic_per_seed():
    for mod, tr in ((open_poisson, STEADY), (open_onoff, BURSTY)):
        a, b, c = (_sched(mod, tr, s) for s in (SEED, SEED, SEED + 1))
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        assert len(a[0]) != len(c[0]) or not np.array_equal(a[0], c[0])


def test_mean_rates():
    for mod, tr in ((open_poisson, STEADY), (open_onoff, BURSTY)):
        due, _ = _sched(mod, tr, SEED)
        assert abs(len(due) / 20.0 / 4000 - 1) < 0.02
        assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 20


def test_burst_shape():
    due, _ = _sched(open_onoff, BURSTY, SEED)
    phase = due % 1.0
    on_rate = np.sum(phase < 0.2) / (20 * 0.2)
    off_rate = np.sum(phase >= 0.2) / (20 * 0.8)
    assert abs(on_rate / 12000 - 1) < 0.05
    assert abs(off_rate / 2000 - 1) < 0.05


def test_zipf_shares_and_moving_hot_set():
    due, tenant = _sched(open_onoff, BURSTY, SEED)
    w = popularity.zipf_weights(10, 1.0)
    hot = []
    for epoch in range(4):
        m = (due >= 5 * epoch) & (due < 5 * (epoch + 1))
        share = np.bincount(tenant[m], minlength=10) / m.sum()
        assert np.allclose(np.sort(share)[::-1], w, atol=0.01)
        hot.append(int(np.argmax(share)))
    assert len(set(hot)) > 1


def test_uniform_shares():
    _, tenant = _sched(open_poisson, STEADY, SEED)
    share = np.bincount(tenant, minlength=10) / len(tenant)
    assert np.allclose(share, 0.1, atol=0.01)


def test_onoff_refuses_factors_that_do_not_average_one():
    bad = dict(BURSTY, off_factor=0.6)
    try:
        _sched(open_onoff, bad, SEED)
    except ValueError:
        return
    raise AssertionError("accepted on/off factors that do not average 1")
