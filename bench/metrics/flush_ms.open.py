"""Median host wall time of one ``flush`` in the window."""

from harness.stats import median_flush_ms


def read(run):
    return median_flush_ms(run)
