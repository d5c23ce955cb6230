"""Median over the window's prefills of the engine's host time of one: the
prompt's forward and first token (``mafia.lm.prefill``) and the insert of
its cache rows into the slot (``mafia.lm.insert``)."""

from harness.lm_stats import median_ms


def read(run):
    return median_ms(getattr(run, "prefills", None), "prefill_s", "insert_s")
