"""Median over the window's decode steps of the engine's host time of one
step: the dispatch of ``lm_decode``, the wait and the tokens' copy, and the
sampling and bookkeeping (the program's ``mafia.lm.decode``)."""

from harness.lm_stats import median_ms


def read(run):
    return median_ms(getattr(run, "decodes", None), "forward_s", "fetch_s",
                     "sample_s")
