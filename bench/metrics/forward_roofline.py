"""The forward's share of its roofline: the least time the chip could take
for the window's forwards over the device time they took.

Least time is the larger of operations over the peak of the configuration's
arithmetic and bytes over HBM bandwidth; operations and bytes are counted
from the source DFG for the real requests each flush carried (``work.py``),
so padding is waste.  Device time is the time of the tenants' forward
programs in the trace."""

from harness.stats import window_flushes


def read(run):
    if run.trace is None:
        return None
    ops = byt = 0.0
    m = window_flushes(run)
    for k, n in zip(run.flushes["tenant"][m], run.flushes["n"][m]):
        o, b = run.tenants[k].work.flush(int(n))
        ops += o
        byt += b
    dev = sum(run.trace.device_s(name)
              for name in {t.forward_module for t in run.tenants})
    if dev <= 0.0 or ops == 0.0:
        return None
    least = max(ops / run.peaks[run.config["peak_ops"]],
                byt / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev
