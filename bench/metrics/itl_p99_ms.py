"""99th percentile of the gap between consecutive tokens of a request, over
the tokens decoded in the window: a step that also prefills new requests
delays every running one, and shows here."""

from harness.lm_stats import token_gaps_ms
from harness.stats import quantile


def read(run):
    gaps = token_gaps_ms(run)
    return None if gaps is None or len(gaps) == 0 else quantile(gaps, 0.99)
