"""99th-percentile due-to-answer latency over all requests due in the
window; a request never answered counts as infinitely late.  A per-layer
reading: a single host stall of ~0.1 s moves it several-fold at low
rates, too far for a bound."""

from harness.stats import latency_ms, quantile


def read(run):
    lat = latency_ms(run)
    return None if lat is None or len(lat) == 0 else quantile(lat, 0.99)
