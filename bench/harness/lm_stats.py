"""What the token path's readers share: the engine's step and prefill
records over the window, and the work they did (``lm_work``)."""

from __future__ import annotations

import numpy as np

from harness import lm_work
from harness.stats import quantile


def median_ms(rec: dict | None, *fields: str) -> float | None:
    """Median over the window's records of the sum of ``fields``, in ms;
    None where the run keeps no such records."""
    if rec is None or len(rec["t0"]) == 0:
        return None
    return quantile(sum(rec[f] for f in fields) * 1e3, 0.5)


def decode_work(run) -> tuple[float, float]:
    """(operations, least seconds) of the window's decode steps."""
    d = getattr(run, "decodes", None)
    if d is None:
        return 0.0, 0.0
    ops = least = 0.0
    for n, rows in zip(d["n_active"], d["ctx_rows"]):
        o, b = lm_work.decode(run.shape, int(n), int(rows))
        ops += o
        least += max(o / run.peaks[run.config["peak_ops"]],
                     b / run.peaks["hbm_bytes_per_s"])
    return ops, least


def prefill_work(run) -> tuple[float, float]:
    """(operations, least seconds) of the window's prefills."""
    p = getattr(run, "prefills", None)
    if p is None:
        return 0.0, 0.0
    ops = least = 0.0
    for plen in p["plen"]:
        o, b = lm_work.prefill(run.shape, int(plen))
        ops += o
        least += max(o / run.peaks[run.config["peak_ops"]],
                     b / run.peaks["hbm_bytes_per_s"])
    return ops, least


def roofline(run, least: float, module: str) -> float | None:
    """Least time over the device time of the programs named ``module``."""
    if run.trace is None or least <= 0.0:
        return None
    dev = run.trace.device_s(module)
    return None if dev <= 0.0 else 100.0 * least / dev


def token_gaps_ms(run) -> np.ndarray | None:
    """The gap before each token a request received from a decode step in
    the window, once per such token: a step's end less the previous step's
    end, for every request it decoded that it did not also admit."""
    st = getattr(run, "steps", None)
    if st is None or len(st["t_end"]) < 2:
        return None
    gap = np.diff(st["t_end"]) * 1e3
    t = st["t_end"][1:]
    n = (st["n_decoded"][1:] - st["n_admitted"][1:]).clip(0).astype(np.int64)
    keep = run.in_window(t) & (n > 0)
    return np.repeat(gap[keep], n[keep])
