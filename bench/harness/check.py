"""The comparison that decides ``correct``.

Every answer a client received in the run is compared with the plain
reference (``models/<family>.py``, float64, on the host) of its input row,
tenant by tenant, after the window has closed.  The configuration's
``limits`` name the numbers it is held to, each with its limit:

* ``score_rms_err``: over the tenants, the largest root-mean-square error
  of the served class scores, as a share of the root-mean-square of the
  reference's scores.  It reads the precision the scores were computed in.
* ``class_gap_mean``: over the tenants, the largest mean of the amount by
  which the reference's score of the served class lies below the
  reference's best score, as a share of the tenant's largest reference
  score.  A class picked on a near tie adds almost nothing; wrong or
  altered classes add their whole gap.
* ``class_miss_share``: the share of all answers whose served class lies
  more than ``MISS_GAP`` of its tenant's largest reference score below the
  reference's best.  It judges every answer on its own, so one wrong
  answer in a full bucket counts as much as one in a bucket of one, where a
  fixed-point program's own rounding only ever flips near ties.
* ``unanswered``: requests sent that never got an answer.

The control computes the same numbers for the reference itself run in the
next lower precision than the configuration states (``control``): int4
power-of-two fixed point for int8, bfloat16 for float32.
"""

from __future__ import annotations

import numpy as np

MISS_GAP = 0.1


def tenant_numbers(ref: np.ndarray, scores: np.ndarray,
                   pred: np.ndarray) -> dict:
    """One tenant's answers against the reference: the rms error share,
    the mean class gap, and how many answers there are and miss by more
    than ``MISS_GAP``."""
    n = len(ref)
    bad = {"score_rms_err": float("inf"), "class_gap_mean": float("inf"),
           "miss": n, "n": n}
    scores = np.asarray(scores, np.float64)
    if scores.shape != ref.shape or not np.all(np.isfinite(scores)):
        return bad
    rms = float(np.sqrt(np.mean((scores - ref) ** 2))
                / max(np.sqrt(np.mean(ref ** 2)), np.finfo(np.float64).tiny))
    pred = np.asarray(pred).ravel()
    if pred.shape != (n,) or np.any(pred < 0) or np.any(
            pred >= ref.shape[1]):
        return dict(bad, score_rms_err=rms)
    scale = max(float(np.max(np.abs(ref))), np.finfo(np.float64).tiny)
    gap = (ref.max(axis=1) - ref[np.arange(n), pred.astype(np.int64)]) / scale
    return {"score_rms_err": rms, "class_gap_mean": float(np.mean(gap)),
            "miss": int(np.sum(gap > MISS_GAP)), "n": n}


def answers(outputs: list, score_key: str, pred_key: str):
    """Stack the clients' answers: (scores, class index)."""
    scores = np.stack([np.asarray(o[score_key]) for o in outputs])
    pred = np.stack([np.asarray(o[pred_key]).ravel()[0] for o in outputs])
    return scores, pred


def numbers(per_tenant: list[dict], unanswered: int) -> dict:
    n = sum(t["n"] for t in per_tenant)
    return {
        "score_rms_err": max((t["score_rms_err"] for t in per_tenant),
                             default=0.0),
        "class_gap_mean": max((t["class_gap_mean"] for t in per_tenant),
                              default=0.0),
        "class_miss_share": sum(t["miss"] for t in per_tenant) / max(n, 1),
        "unanswered": float(unanswered)}


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """Correct when every number the configuration limits is at or under
    its limit; those numbers beside their limits, for the result line."""
    shown = {k: {"value": nums[k], "limit": float(v)}
             for k, v in limits.items()}
    ok = all(np.isfinite(s["value"]) and s["value"] <= s["limit"]
             for s in shown.values())
    return ok, shown


# ------------------------------------------------------------------ control
def rounder(control: dict, calib_max: dict | None = None):
    """The rounding of the control: every weight and every intermediate
    tensor of the reference goes through it, by name."""
    kind = control["kind"]
    if kind == "bfloat16":
        import ml_dtypes

        def r(name, a):
            return (np.asarray(a, np.float32).astype(ml_dtypes.bfloat16)
                    .astype(np.float64))
        return r
    if kind == "pow2_fixed":
        qmax = 2 ** (int(control["bits"]) - 1) - 1

        def r(name, a):
            m = calib_max.get(name) if calib_max else None
            m = float(np.max(np.abs(a))) if m is None else m
            if m == 0.0:
                return a
            e = np.floor(np.log2(qmax / m))
            return np.clip(np.round(a * 2.0 ** e), -qmax, qmax) / 2.0 ** e
        return r
    raise ValueError(f"unknown control {kind!r}")


def calib_maxima(forward, params, tenant: dict, calib: np.ndarray) -> dict:
    """Largest magnitude of every named tensor of the reference over the
    calibration rows: the control's fixed-point ranges."""
    seen: dict[str, float] = {}

    def rec(name, a):
        seen[name] = max(seen.get(name, 0.0), float(np.max(np.abs(a))))
        return a

    forward(params, tenant, calib, rec)
    return seen
