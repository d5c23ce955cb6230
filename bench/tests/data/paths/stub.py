"""A stand-in serving path for the harness's own tests: a numpy "engine"
that answers each row ``x`` with ``W @ x`` in float32, driven in batches
of ``batch`` rows for the window by a traffic kind of its own
(``stub_loop``), and compared with the same map in float64.

It shows that a configuration naming ``"path": "stub"`` is served by this
file alone through ``cell.run``: the harness knows nothing of it."""

from __future__ import annotations

import dataclasses

import numpy as np

from harness import session, trace
from harness.peaks import peaks
from harness.session import CLOCK


@dataclasses.dataclass
class Setup:
    config: dict
    weights: np.ndarray        # the engine's float32 weights
    peaks: dict


@dataclasses.dataclass
class Log:
    due: np.ndarray            # seconds from the window's open
    t_done: np.ndarray
    row: np.ndarray
    answers: np.ndarray


@dataclasses.dataclass
class Run:
    config: dict
    seconds: float
    setup_s: float
    log: Log
    peaks: dict
    trace: trace.Summary | None

    def in_window(self, t: np.ndarray) -> np.ndarray:
        return (t >= 0.0) & (t <= self.seconds)


def _weights(cfg: dict, dtype) -> np.ndarray:
    rng = np.random.default_rng(cfg["weight_seed"])
    return rng.standard_normal((cfg["n_out"], cfg["n_in"])).astype(dtype)


def set_up(cfg: dict, device: dict) -> Setup:
    return Setup(cfg, _weights(cfg, np.float32), peaks(device["kind"]))


def rows(su: Setup, seed: int) -> np.ndarray:
    cfg = su.config
    return np.random.default_rng([seed, 1]).standard_normal(
        (cfg["rows"], cfg["n_in"])).astype(np.float32)


def measure(su: Setup, cell: dict, traffic: dict, pool: np.ndarray,
            seed: int, seconds: float, traced: bool, t_start: float) -> Run:
    if traffic["kind"] != "stub_loop":
        raise ValueError(f"the stub path serves stub_loop, not "
                         f"{traffic['kind']!r}")
    batch = int(traffic["batch"])
    rng = np.random.default_rng([seed, 2])
    due, done, row, answers = [], [], [], []
    with session.Session(traced) as sess:
        setup_s = CLOCK() - t_start
        t0 = CLOCK()
        sess.mark(trace.OPEN)
        while (now := CLOCK()) < t0 + seconds:
            idx = rng.integers(0, len(pool), batch)
            out = pool[idx] @ su.weights.T
            t = CLOCK()
            due += [now - t0] * batch
            done += [t - t0] * batch
            row.append(idx)
            answers.append(out)
        sess.mark(trace.CLOSE)
    log = Log(np.array(due), np.array(done), np.concatenate(row),
              np.concatenate(answers))
    return Run(su.config, seconds, setup_s, log, su.peaks, sess.summary)


def numbers(r: Run, pool: np.ndarray, control: bool = False
            ) -> tuple[dict, int, int]:
    """The largest error of an answer as a share of the reference's largest
    magnitude; the control computes the map in float16."""
    ref = pool[r.log.row].astype(np.float64) @ _weights(
        r.config, np.float64).T
    got = r.log.answers
    if control:
        got = (pool[r.log.row].astype(np.float16)
               @ _weights(r.config, np.float16).T)
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    sent = r.in_window(r.log.due)
    failed = int(np.sum(sent & ~np.isfinite(r.log.t_done)))
    return ({"max_rel_err": err, "unanswered": float(failed)},
            int(np.sum(sent)), failed)
