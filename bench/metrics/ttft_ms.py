"""Median, over the requests sent in the window that got a first token, of
the time from submit to it (the engine's ``Request.ttft_s``)."""

import numpy as np

from harness.stats import quantile


def read(run):
    log = run.log
    t_first = getattr(log, "t_first", None)
    if t_first is None:
        return None
    m = run.in_window(log.due) & np.isfinite(t_first)
    if not np.any(m):
        return None
    return quantile((t_first[m] - log.due[m]) * 1e3, 0.50)
