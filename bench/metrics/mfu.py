"""The whole step's share of the chip's peak: operations of the requests
answered in the window (counted from the source DFG) per second of the
window, over the peak of the configuration's arithmetic."""

import numpy as np


def read(run):
    log = run.log
    done = run.in_window(log.t_done)
    ops = sum(float(np.sum(done & (log.tenant == k))) * t.work.ops_per_request
              for k, t in enumerate(run.tenants))
    return 100.0 * ops / run.seconds / run.peaks[run.config["peak_ops"]]
