"""Median over the window's flushes of the wait for the device and the
device-to-host copy of the outputs (the program's ``mafia.flush.fetch``)."""

from harness.flush_records import median_ms


def read(run):
    return median_ms(run, "fetch")
