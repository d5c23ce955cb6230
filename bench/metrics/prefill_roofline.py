"""The prefill's share of its roofline: the least time of the window's
``lm_prefill`` programs (operations over the bf16 peak or bytes over HBM
bandwidth, the larger, prompt by prompt; real prompt tokens only, the
padding to the bucket is waste, ``harness/lm_work.py``) over their device
time in the trace."""

from harness.lm_stats import prefill_work, roofline


def read(run):
    _, least = prefill_work(run)
    return roofline(run, least, "jit_lm_prefill")
