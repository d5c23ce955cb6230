"""The token path's work count (``harness/lm_work.py``), checked by hand for
one decode step and one prefill of DeepSeek-V2-Lite cut to five layers."""

from __future__ import annotations

import json

import pytest

from conftest import BENCH
from harness import lm_work

CFG = json.loads((BENCH / "configs" / "lm" / "dsv2-lite-l5-bf16.json")
                 .read_text())


def test_shape_from_published_keys():
    s = lm_work.shape(CFG)
    assert (s.d, s.heads, s.nope, s.rope, s.v, s.rank) == (
        2048, 16, 128, 64, 128, 512)
    assert (s.n_dense, s.n_moe, s.experts, s.k, s.shared) == (1, 4, 64, 6, 2)
    assert s.cache_row_bytes == 576 * 2


def _weights(reached_experts: float) -> float:
    # per layer: W_q 2048*16*192, W_dkv 2048*512, W_kr 2048*64,
    # W_o 16*128*2048, W_uk and W_uv 512*16*128 each
    attn = 2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 2048 + 2 * 512 * 16 * 128
    dense = 3 * 2048 * 10944
    expert = 3 * 2048 * 1408
    moe = reached_experts * expert + 2 * expert
    router = 2048 * 64 * 2              # float32: two bf16 widths
    head = 2048 * 102400
    return 2 * (5 * attn + dense + 4 * (moe + router) + head)


def test_decode_step_by_hand():
    s = lm_work.shape(CFG)
    n, rows = 128, 128 * 2048
    ops, byt = lm_work.decode(s, n, rows)
    proj = 2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 2048
    absorb = 16 * 128 * 512 * 2                 # q into the latent, out of it
    ffn = 2 * 3 * 2048 * 10944 + 4 * 2 * (8 * 3 * 2048 * 1408 + 2048 * 64)
    token = 2 * 5 * (proj + absorb) + ffn + 2 * 2048 * 102400
    per_row = 2 * 16 * (512 + 64) + 2 * 16 * 512   # scores, latent context
    assert ops == pytest.approx(n * token + 5 * rows * per_row, rel=1e-12)
    reached = 64 * (1 - (63 / 64) ** (n * 6))
    cache = 5 * (rows + n) * 576 * 2
    assert byt == pytest.approx(_weights(reached) + n * 2048 * 2 + cache,
                                rel=1e-12)
    # weight-bound: about 5.3 GB of weights and 1.5 GB of real cache rows
    assert 6.5e9 < byt < 7.2e9


def test_prefill_by_hand():
    s = lm_work.shape(CFG)
    p = 2048
    ops, byt = lm_work.prefill(s, p)
    proj = 2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 2048
    up = 512 * 16 * (128 + 128)                 # keys and values per head
    ffn = 2 * 3 * 2048 * 10944 + 4 * 2 * (8 * 3 * 2048 * 1408 + 2048 * 64)
    attn = 2 * 5 * 16 * (192 + 128) * (p * (p + 1) // 2)
    assert ops == pytest.approx(p * (2 * 5 * (proj + up) + ffn) + attn
                                + 2 * 2048 * 102400, rel=1e-12)
    # ~1.8 TFLOP a 2k prompt: the head runs at its last position only
    assert 1.7e12 < ops < 1.9e12
    reached = 64 * (1 - (63 / 64) ** (p * 6))
    assert byt == pytest.approx(_weights(reached) + p * 2048 * 2
                                + 5 * p * 576 * 2, rel=1e-12)
    assert lm_work.decode(s, 0, 0) == (0.0, 0.0)
