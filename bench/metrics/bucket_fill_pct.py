"""Real requests over bucket rows, summed over the window's flushes."""

import numpy as np

from harness.stats import window_flushes


def read(run):
    m = window_flushes(run)
    rows = np.sum(run.flushes["bucket"][m])
    return None if rows == 0 else 100.0 * np.sum(run.flushes["n"][m]) / rows
