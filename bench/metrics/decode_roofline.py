"""The decode step's share of its roofline: the least time of the window's
``lm_decode`` programs (operations over the bf16 peak or bytes over HBM
bandwidth, the larger, step by step; real tokens and the cache rows up to
each slot's position only, ``harness/lm_work.py``) over their device time
in the trace."""

from harness.lm_stats import decode_work, roofline


def read(run):
    _, least = decode_work(run)
    return roofline(run, least, "jit_lm_decode")
