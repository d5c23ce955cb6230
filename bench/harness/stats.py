"""Statistics the metric readers share."""

from __future__ import annotations

import math

import numpy as np


def quantile(x: np.ndarray, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least a share
    ``q`` of the sample at or below it.  Infinite values (requests never
    answered) sort last."""
    x = np.sort(np.asarray(x, np.float64))
    if len(x) == 0:
        raise ValueError("empty sample")
    return float(x[max(0, math.ceil(q * len(x)) - 1)])


def latency_ms(run) -> np.ndarray | None:
    """Due-to-answer latency of every request due in the window, infinite
    for one never answered; None in a closed loop, where a request is due
    only when its client has its previous answer."""
    log = run.log
    if not log.open_loop:
        return None
    due = run.in_window(log.due)
    lat = (log.t_done[due] - log.due[due]) * 1e3
    return np.where(np.isfinite(lat), lat, np.inf)


def window_flushes(run) -> np.ndarray:
    """Mask of the flushes that started inside the window."""
    return run.in_window(run.flushes["t0"])


def median_flush_ms(run) -> float | None:
    """Median host wall time of one ``flush`` in the window: stack, the
    numpy pad to the bucket, the forward's dispatch with the rows' copy to
    the device, the device wait and the outputs' copy back, the slice on the
    host, scatter."""
    m = window_flushes(run)
    if not np.any(m):
        return None
    return quantile((run.flushes["t1"][m] - run.flushes["t0"][m]) * 1e3, 0.5)
