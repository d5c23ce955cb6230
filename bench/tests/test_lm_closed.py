"""The token path's closed loop (``loadgen/lm_closed.py``): each length
follows the mix's law, and the stratified draws give every seed nearly
the same amount of work."""

from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import BENCH

TRAFFIC = json.loads((BENCH / "traffic" / "decode-heavy.json").read_text())
PROMPT, OUTPUT = TRAFFIC["prompt_len"], TRAFFIC["output_len"]
SEEDS = [2**31 + 17 * k for k in range(24)]


def _independent(spec: dict, rng, n: int) -> np.ndarray:
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"])


@pytest.mark.parametrize("n", [6, 16, 128])
def test_strata_put_one_quantile_in_each(n):
    from loadgen.lm_closed import strata

    u = strata(np.random.default_rng(SEEDS[0]), n)
    assert sorted(np.floor(u * n).astype(int)) == list(range(n))


@pytest.mark.parametrize("which", ["prompt", "output", "residual"])
def test_lengths_follow_the_mix(which):
    """At uniform quantiles, each law matches independent draws of it: a
    clipped, rounded log-normal, and a uniform share of one, rounded up."""
    from loadgen.lm_closed import lengths, residuals

    rng = np.random.default_rng(SEEDS[1])
    n = 200_000
    u = rng.uniform(size=n)
    if which == "residual":
        got = residuals(OUTPUT, u)
        want = np.maximum(1, np.ceil(rng.uniform(size=n)
                                     * _independent(OUTPUT, rng, n)))
        at = [1, 8, 32, 64, 128, 256, 512, 1024]
    else:
        spec = PROMPT if which == "prompt" else OUTPUT
        got = lengths(spec, u)
        want = _independent(spec, rng, n)
        at = [spec["min"], spec["median"] // 2, spec["median"],
              2 * spec["median"], spec["max"] - 1, spec["max"]]
    for k in at:
        assert abs(np.mean(got <= k) - np.mean(want <= k)) < 0.006, k


def test_every_seed_sends_nearly_the_same_work():
    """Over the first requests, the prompt tokens and the count left with
    at most a given number of tokens vary far less across seeds than with
    independent draws, yet the draws differ from seed to seed."""
    from loadgen.lm_closed import lengths, residuals, strata

    n = TRAFFIC["outstanding"]
    tokens, short, firsts = [], [], set()
    for seed in SEEDS:
        rng = np.random.default_rng(seed)
        plen = lengths(PROMPT, strata(rng, n))
        left = residuals(OUTPUT, strata(rng, n))
        tokens.append(plen.sum())
        short.append(np.sum(left <= 128))
        firsts.add((int(plen[0]), int(left[0])))
    rng = np.random.default_rng(SEEDS[2])
    indep = [_independent(PROMPT, rng, n).sum() for _ in SEEDS]
    assert len(firsts) > len(SEEDS) // 2
    assert np.std(tokens) / np.mean(tokens) < 0.2 * (np.std(indep)
                                                     / np.mean(indep))
    assert max(short) - min(short) <= 2
