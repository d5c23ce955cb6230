"""The comparison that decides ``correct``: what each number reads, and
that a configuration is held to the numbers its ``limits`` name."""

from __future__ import annotations

import numpy as np

from harness import check

REF = np.array([[1.0, 0.0, -1.0],
                [0.50, 0.49, 0.0],
                [0.0, 2.0, 1.0],
                [0.3, 0.2, 0.1]])
BEST = REF.argmax(axis=1)


def test_sound_answers_read_zero():
    t = check.tenant_numbers(REF, REF, BEST)
    assert t["score_rms_err"] == 0.0 and t["class_gap_mean"] == 0.0
    assert t["miss"] == 0 and t["n"] == 4


def test_near_tie_is_no_miss_and_wrong_class_is():
    pred = BEST.copy()
    pred[1] = 1                 # 0.01 below the best: a near tie
    assert check.tenant_numbers(REF, REF, pred)["miss"] == 0
    pred[2] = 0                 # 2.0 below the best, all of the scale
    t = check.tenant_numbers(REF, REF, pred)
    assert t["miss"] == 1
    assert np.isclose(t["class_gap_mean"], (0.01 + 2.0) / 2.0 / 4)


def test_miss_share_pools_every_answer():
    a = {"score_rms_err": 0.1, "class_gap_mean": 0.0, "miss": 1, "n": 10}
    b = {"score_rms_err": 0.2, "class_gap_mean": 0.3, "miss": 0, "n": 30}
    nums = check.numbers([a, b], 0)
    assert nums["class_miss_share"] == 1 / 40
    assert nums["score_rms_err"] == 0.2 and nums["class_gap_mean"] == 0.3


def test_malformed_answers_read_infinite():
    t = check.tenant_numbers(REF, REF[:, :2], BEST)
    assert t["score_rms_err"] == float("inf") and t["miss"] == t["n"]
    t = check.tenant_numbers(REF, REF, BEST + 5)
    assert t["class_gap_mean"] == float("inf")


def test_verdict_holds_only_the_limited_numbers():
    nums = {"score_rms_err": 0.1, "class_gap_mean": 9.0,
            "class_miss_share": 0.0, "unanswered": 0.0}
    ok, shown = check.verdict(nums, {"score_rms_err": 0.5,
                                     "class_miss_share": 0.002,
                                     "unanswered": 0})
    assert ok and list(shown) == ["score_rms_err", "class_miss_share",
                                  "unanswered"]
    ok, _ = check.verdict(dict(nums, unanswered=1.0), {"unanswered": 0})
    assert not ok
