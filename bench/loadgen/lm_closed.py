"""Closed loop of token requests: ``outstanding`` sequences in flight, one
per engine slot, each replaced by the next as soon as it completes, so that
every decode step runs a full batch.

Prompt and output lengths are log-normal (``median``, ``sigma``), clipped to
``[min, max]``, drawn from the run's seed; outputs are greedy with no early
stop, so a request's length is its ``max_new_tokens``.  The first
``outstanding`` requests are sent ``preroll_s`` before the window opens,
each with a uniform share of a drawn output length left to run, so that
they finish spread out as in a steady state.  After the window closes
nothing more is sent and the loop steps until every request sent has
completed.

The draws are stratified: the first ``outstanding`` requests, and each
later block of ``BLOCK``, take one quantile from each of as many equal
strata of the law, in a seeded order and at a seeded point of the stratum.
Each request's length still follows the law, but every seed sends nearly
the same amount of work, as ``open_poisson`` sends the same number of
requests in another order.  A window holds only some tens of
completions, so with independent draws the seed alone moved their count
by ~10%.

The path's context provides ``clock``, ``t0``, ``seconds``, ``grace_s``,
``rng`` (seeded by the run's seed), ``send(n_prompt, max_new_tokens)``,
``step()`` (returns how many requests completed) and ``in_flight``.
"""

from __future__ import annotations

import time
from statistics import NormalDist

import numpy as np

BLOCK = 16      # the later requests are drawn in strata of this many
_NORMAL = NormalDist()


def strata(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` uniform quantiles, one in each of ``n`` equal strata of
    (0, 1), in a seeded order."""
    return (rng.permutation(n) + rng.uniform(size=n)) / n


def lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """The log-normal lengths of ``spec`` (median, sigma, min, max) at
    quantiles ``u``."""
    z = np.array([_NORMAL.inv_cdf(min(max(float(q), 1e-12), 1.0 - 1e-12))
                  for q in u])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def residuals(spec: dict, u: np.ndarray) -> np.ndarray:
    """What is left to run of requests caught part-way, at quantiles ``u``:
    the law of ``ceil(v * L)`` for ``v`` uniform on (0, 1) and ``L`` a
    length of ``spec``, since ``ceil(v * L) <= k`` exactly when
    ``v <= k / L``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    el = np.arange(lo, hi + 1)
    mu, sigma = np.log(spec["median"]), spec["sigma"]
    cdf = np.array([_NORMAL.cdf((np.log(x) - mu) / sigma)
                    for x in np.arange(lo, hi + 1) + 0.5])
    cdf[-1] = 1.0                            # clipped from above
    p_len = np.diff(cdf, prepend=0.0)        # clipped from below into lo
    k = np.arange(1, hi + 1)
    law = np.minimum(k[:, None] / el[None, :], 1.0) @ p_len
    return k[np.minimum(np.searchsorted(law, u), len(k) - 1)]


def drive(ctx, traffic: dict) -> None:
    n = int(traffic["outstanding"])
    rng = ctx.rng
    prompt, output = traffic["prompt_len"], traffic["output_len"]
    pending: list = []

    def draw(k: int) -> list:
        while len(pending) < k:
            pending.extend(zip(lengths(prompt, strata(rng, BLOCK)),
                               lengths(output, strata(rng, BLOCK))))
        out = pending[:k]
        del pending[:k]
        return out

    close = ctx.t0 + ctx.seconds
    start = ctx.t0 - float(traffic["preroll_s"])
    time.sleep(max(0.0, start - ctx.clock()))
    first = zip(lengths(prompt, strata(rng, n)),
                residuals(output, strata(rng, n)))
    for plen, left in first:
        ctx.send(int(plen), int(left))
    while ctx.in_flight:
        if ctx.clock() > close + ctx.grace_s:
            return          # what is still in flight counts unanswered
        done = ctx.step()
        if done and ctx.clock() < close:
            for plen, out in draw(done):
                ctx.send(int(plen), int(out))
