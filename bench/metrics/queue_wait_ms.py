"""Median, over the requests due in the window, of the start of the flush
that carried each one less the time it was due."""

import numpy as np

from harness.stats import quantile


def read(run):
    log = run.log
    if not log.open_loop:
        return None
    m = run.in_window(log.due) & np.isfinite(log.t_flush)
    if not np.any(m):
        return None
    return quantile((log.t_flush[m] - log.due[m]) * 1e3, 0.50)
