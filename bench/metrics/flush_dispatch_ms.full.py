"""Median over the window's flushes of the batch lane's host time: copy
and pad to the bucket, dispatch of the forward, slice (the program's
``mafia.batch.put`` + ``mafia.batch.forward`` + ``mafia.batch.slice``)."""

from harness.flush_records import median_ms


def read(run):
    return median_ms(run, "put", "forward", "slice")
