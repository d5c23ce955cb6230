"""The token path's share of the chip's bf16 peak: the model operations of
the real tokens the window's prefills and decode steps computed
(``harness/lm_work.py``), per second of the window, over the peak."""

from harness.lm_stats import decode_work, prefill_work


def read(run):
    if getattr(run, "decodes", None) is None:
        return None
    ops = decode_work(run)[0] + prefill_work(run)[0]
    return 100.0 * ops / run.seconds / run.peaks[run.config["peak_ops"]]
