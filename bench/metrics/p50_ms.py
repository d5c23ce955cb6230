"""Median due-to-answer latency over all requests due in the window."""

from harness.stats import latency_ms, quantile


def read(run):
    lat = latency_ms(run)
    return None if lat is None or len(lat) == 0 else quantile(lat, 0.50)
