"""Median over the window's flushes of how late the engine's loop came to
a queue after its policy made it due (``late_us`` of ``mafia.flush``): the
wait behind other tenants' flushes."""

from harness.flush_records import median_ms


def read(run):
    return median_ms(run, "late_s")
