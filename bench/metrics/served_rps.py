"""Requests answered inside the window, per second of the window."""

import numpy as np


def read(run):
    return float(np.sum(run.in_window(run.log.t_done))) / run.seconds
