"""Median over the window's flushes of the per-request Python around the
forward: stacking the rows and scattering the answers (the program's
``mafia.flush.stack`` + ``mafia.flush.scatter``)."""

from harness.flush_records import median_ms


def read(run):
    return median_ms(run, "stack", "scatter")
