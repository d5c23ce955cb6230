"""Median over the window's flushes of the batch lane's host time: the
numpy pad to the bucket (none on a full one), the forward's dispatch with
the host-to-device copy of the rows, and ``[:n]`` of the outputs on the
host (the program's ``mafia.batch.put`` + ``mafia.batch.forward`` +
``mafia.batch.slice``)."""

from harness.flush_records import median_ms


def read(run):
    return median_ms(run, "put", "forward", "slice")
