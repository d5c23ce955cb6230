"""How late the load generator sent: 99th percentile, over the requests
due in the window, of the send time less the due time."""

import numpy as np

from harness.stats import quantile


def read(run):
    log = run.log
    if not log.open_loop:
        return None
    m = run.in_window(log.due) & np.isfinite(log.t_submit)
    if not np.any(m):
        return None
    return quantile((log.t_submit[m] - log.due[m]) * 1e3, 0.99)
