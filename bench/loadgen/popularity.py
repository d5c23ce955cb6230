"""Which tenant each request goes to.

``uniform``: every tenant alike.  ``zipf``: tenant of rank r has weight
1 / r^s; every ``rotate_s`` seconds a seeded permutation moves ranks onto
other tenants, so the hot set moves.  Each rank gets its share of the
requests exactly, in a seeded order, so every seed sends each rank the
same number.
"""

from __future__ import annotations

import numpy as np


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def assign(t: np.ndarray, n_tenants: int, spec: dict,
           rng: np.random.Generator) -> np.ndarray:
    """Tenant index for requests due at times ``t`` (seconds from the
    window's start)."""
    kind = spec["kind"]
    if kind == "uniform":
        p = np.full(n_tenants, 1.0 / n_tenants)
    elif kind == "zipf":
        p = zipf_weights(n_tenants, spec["s"])
    else:
        raise ValueError(f"unknown popularity {kind!r}")
    rank = rng.permutation(_apportion(p, len(t)))
    if kind == "uniform":
        return rank
    epoch = np.floor(t / spec["rotate_s"]).astype(np.int64)
    n_epochs = int(epoch.max(initial=-1)) + 2
    perms = np.stack([rng.permutation(n_tenants) for _ in range(n_epochs)])
    return perms[np.clip(epoch, 0, None), rank]


def _apportion(p: np.ndarray, n: int) -> np.ndarray:
    """``n`` ranks, rank ``r`` as often as ``p[r] * n`` rounds to (largest
    remainders first)."""
    exact = p * n
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(counts - exact, kind="stable")[:short]] += 1
    return np.repeat(np.arange(len(p)), counts)
