"""DeepSeek-V2-Lite on the token path: the serving engine against the
benchmark's plain reference (``bench/models/deepseek_v2.py``) on the
published checkpoint layout drawn from a seed and loaded into SMOKE's tree
(``bench/paths/lm.py``), the dropless expert layer, the router's published
gating, YaRN, the dense then MoE stacks, the last-position head, and the
engine's donated cache, records and counters."""

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.models.layers import Initializer, rope_freqs, rope_table
from repro.serve.engine import DECODE_FIELDS, PREFILL_FIELDS, ServeEngine

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from models import deepseek_v2 as ref  # noqa: E402  the plain reference
from paths import lm  # noqa: E402  the published checkpoint's loader

RNG = np.random.default_rng(0)
SMOKE = get_arch("deepseek-v2-lite").smoke


def _published(cfg, dtype="float32", seed=1):
    """``cfg`` as the published config.json keys, with the checkpoint's
    dtype and seed."""
    rs = cfg.rope_scaling
    return {
        "name": cfg.name, "model_type": "deepseek_v2", "hidden_act": "silu",
        "scoring_func": "softmax", "topk_method": "greedy",
        "q_lora_rank": None, "attention_bias": False,
        "tie_word_embeddings": False, "moe_layer_freq": 1, "n_group": 1,
        "topk_group": 1, "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_dense_layers,
        "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.d_ff_expert,
        "n_routed_experts": cfg.n_experts,
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "qk_nope_head_dim": cfg.d_head, "qk_rope_head_dim": cfg.d_rope,
        "v_head_dim": cfg.d_head, "kv_lora_rank": cfg.kv_lora_rank,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": rs.factor,
            "original_max_position_embeddings": rs.original_max_position,
            "beta_fast": rs.beta_fast, "beta_slow": rs.beta_slow,
            "mscale": rs.mscale, "mscale_all_dim": rs.mscale_all_dim},
        "vocab_size": cfg.vocab_size, "dtype": dtype, "weight_seed": seed}


# The engine computes in its activation dtype against a float32 reference at
# full precision on the same checkpoint: float32 agrees to rounding order
# (1e-4 of the largest logit); bfloat16 carries one rounding of every matmul
# operand through three layers: 0.9-4.1% of the logits' RMS over eight
# checkpoint seeds, where a token near a routing tie may pick another of
# only 8 experts.  8% leaves room on every seed; a float8 reference reads
# 18-33%.
@pytest.mark.parametrize("act_dtype,tol", [("float32", 1e-4),
                                           ("bfloat16", 8e-2)])
def test_engine_prefill_then_decode_matches_reference(act_dtype, tol):
    cfg = dataclasses.replace(SMOKE, act_dtype=act_dtype,
                              param_dtype=act_dtype)
    # both sides take the same (rounded) checkpoint, as on the chip
    pub = _published(cfg, act_dtype)
    eng = ServeEngine(cfg, lm.load_params(pub, cfg), max_batch=3,
                      max_len=64)
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (13, 7, 21)]
    for p in prompts:
        eng.submit(p, max_new_tokens=6, capture=5)
    for r in eng.run_to_completion():
        plen = len(r.prompt)
        want = np.asarray(ref.forward(ref.spec(pub), pub["weight_seed"],
                                      r.prompt + r.tokens[:5],
                                      np.arange(plen - 1, plen + 5)))
        got = np.stack(r.logits)
        assert got.shape == want.shape == (6, cfg.vocab_size)
        if act_dtype == "float32":
            err = np.abs(got - want).max() / np.abs(want).max()
        else:
            err = np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))
        assert err < tol, f"request {r.rid}: {err}"
        # the tokens served are the greedy picks of the logits kept
        assert r.tokens[:6] == list(got.argmax(axis=1))


@pytest.mark.parametrize("change", [
    {"n_shared_experts": 0}, {"n_shared_experts": 1}, {"n_dense_layers": 0},
    {"n_experts": 4}, {"d_ff_expert": 16}, {"d_ff": 64},
    {"kv_lora_rank": 16}, {"n_heads": 2, "n_kv_heads": 2}],
    ids=lambda c: ",".join(f"{k}={v}" for k, v in c.items()))
def test_checkpoint_refuses_a_program_tree_that_differs(change):
    """The published checkpoint fills the program's tree only where every
    layer, expert and width is there as the config says."""
    with pytest.raises(ValueError):
        lm.load_params(_published(SMOKE), dataclasses.replace(SMOKE,
                                                              **change))


def test_checkpoint_rope_columns_follow_the_published_pairs():
    """Loaded without gathering the rope columns into halves, the program
    rotates other pairs than the published code and leaves the
    reference."""
    pub = _published(SMOKE)
    params = lm.load_params(pub, SMOKE)
    toks = RNG.integers(0, SMOKE.vocab_size, 40)
    want = np.asarray(ref.forward(ref.spec(pub), 1, toks, np.arange(40)))
    dr = SMOKE.d_rope
    back = np.argsort(np.concatenate([np.arange(0, dr, 2),
                                      np.arange(1, dr, 2)]))
    errs = []
    for undo in (False, True):
        p = params
        if undo:
            p = jax.tree.map(lambda a: a, params)
            for st in ("dense_blocks", "blocks"):
                at = dict(p[st]["attn"])
                at["w_qr"] = at["w_qr"][..., back]
                at["w_kr"] = at["w_kr"][..., back]
                p[st] = dict(p[st], attn=at)
        logits, _, _ = T.forward_full(p, SMOKE, jnp.asarray(toks)[None])
        got = np.asarray(logits[0])
        errs.append(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    assert errs[0] < 1e-4 < 0.05 < errs[1]


def _per_token_topk(p, x, k, norm, scale):
    """Every token's chosen experts summed by hand, in float64."""
    xt = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = xt @ np.asarray(p["router"], np.float64)
    gates = np.exp(logits - logits.max(1, keepdims=True))
    gates /= gates.sum(1, keepdims=True)
    out = np.zeros_like(xt)

    def swiglu(h, wg, wu, wd):
        g, u = h @ wg, h @ wu
        return (g / (1 + np.exp(-g)) * u) @ wd

    for t in range(len(xt)):
        top = np.argsort(-gates[t], kind="stable")[:k]
        g = gates[t, top] / (gates[t, top].sum() if norm else 1.0) * scale
        for e, w in zip(top, g):
            out[t] += w * swiglu(xt[t], *(np.asarray(p[n][e], np.float64)
                                          for n in ("w_gate", "w_up",
                                                    "w_down")))
        sh = p["shared"]
        out[t] += swiglu(xt[t], *(np.asarray(sh[n], np.float64)
                                  for n in ("w_gate", "w_up", "w_down")))
    return out.reshape(x.shape)


def _moe_params(E=8, D=16, F=8, seed=0):
    return moe_mod.init_moe(Initializer(jax.random.key(seed)), D, F, E,
                            n_shared=2)


def test_dropless_loses_no_token_under_skewed_routing():
    """Every token routed to one expert first (a capacity layer would drop
    most of them): each still gets its whole top-k sum."""
    p = _moe_params()
    p["router"] = p["router"].at[:, 3].add(5.0)       # expert 3 always wins
    x = jnp.asarray(RNG.normal(size=(2, 24, 16)) + 1.0, jnp.float32)
    out, counts = moe_mod.moe_dropless(p, x, k=3)
    want = _per_token_topk(p, x, 3, False, 1.0)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)
    counts = np.asarray(counts)
    assert counts[3] == 48 and counts.sum() == 48 * 3
    # the capacity layer at its default factor drops tokens here
    capped, _ = moe_mod.moe_ffn(p, x, k=3, norm_topk_prob=False)
    assert np.abs(np.asarray(capped) - want).max() > 1e-2


@pytest.mark.parametrize("norm,scale", [(False, 1.0), (False, 2.5),
                                        (True, 1.0)])
def test_router_gates_norm_and_scaling(norm, scale):
    p = _moe_params(seed=1)
    x = jnp.asarray(RNG.normal(size=(1, 10, 16)), jnp.float32)
    out, _ = moe_mod.moe_dropless(p, x, k=2, norm_topk_prob=norm,
                                  routed_scaling_factor=scale)
    np.testing.assert_allclose(np.asarray(out),
                               _per_token_topk(p, x, 2, norm, scale),
                               rtol=2e-4, atol=2e-4)
    if not norm:        # unnormalised gates are not the renormalised ones
        renorm, _ = moe_mod.moe_dropless(p, x, k=2, norm_topk_prob=True,
                                         routed_scaling_factor=scale)
        assert np.abs(np.asarray(renorm) - np.asarray(out)).max() > 1e-3


def test_dropless_counts_only_masked_tokens():
    p = _moe_params()
    x = jnp.asarray(RNG.normal(size=(1, 10, 16)), jnp.float32)
    mask = jnp.arange(10)[None, :] < 4
    _, counts = moe_mod.moe_dropless(p, x, k=2, count_mask=mask)
    assert int(np.sum(counts)) == 4 * 2
    np.testing.assert_array_equal(
        counts, moe_mod.expert_counts(p, x, k=2, mask=mask))


def test_yarn_by_hand():
    """DeepSeek-V2-Lite's rope: 64 dims, θ 1e4, factor 40 over 4,096,
    β 32 / 1, mscale = mscale_all_dim = 0.707."""
    y = get_arch("deepseek-v2-lite").model.rope_scaling
    dim, theta = 64, 1e4
    # correction dims: 64·ln(4096 / (β·2π)) / (2·ln 1e4)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(1e4)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(1e4)))
    assert (low, high) == (10, 23)
    base = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    want = []
    for i, f in enumerate(base):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / 40 * ramp + f * (1 - ramp))
    np.testing.assert_allclose(np.asarray(rope_freqs(dim, theta, y)), want,
                               rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_freqs(dim, theta, {
        "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1}), want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert y.cos_sin_scale == pytest.approx(1.0)
    assert y.softmax_factor == pytest.approx(m * m)
    assert get_arch("deepseek-v2-lite").model.mla_scale == pytest.approx(
        192 ** -0.5 * m * m)
    cos, _ = rope_table(3, dim, theta, scaling=y)
    np.testing.assert_allclose(np.asarray(cos[2]), np.cos(2 * np.array(want)),
                               rtol=1e-5)


def test_dense_then_moe_stacks():
    params = T.init_params(SMOKE, jax.random.key(0))
    assert "mlp" in params["dense_blocks"] and "moe" in params["blocks"]
    assert params["dense_blocks"]["mlp"]["w_gate"].shape == (1, 64, 96)
    assert params["blocks"]["moe"]["w_gate"].shape == (2, 8, 64, 32)
    toks = jnp.asarray(RNG.integers(0, SMOKE.vocab_size, (2, 12)), jnp.int32)
    logits, _, _ = T.forward_full(params, SMOKE, toks)
    _, cache, _ = T.forward_full(params, SMOKE, toks[:, :-1],
                                 return_cache=True)
    assert cache["ckv"].shape == (3, 2, 11, SMOKE.kv_lora_rank)
    cache = {k: jnp.pad(v, ((0, 0), (0, 0), (0, 1), (0, 0)))
             for k, v in cache.items()}
    ld, new = T.forward_decode(params, SMOKE, toks[:, -1], cache,
                               jnp.full((2,), 11, jnp.int32))
    rel = float(jnp.abs(ld - logits[:, -1]).max() / jnp.abs(logits).max())
    assert rel < 1e-4
    assert new["ckv"].shape == (3, 2, 12, SMOKE.kv_lora_rank)
    # a dense layer in front changes the function
    flat = dict(params, blocks=params["blocks"])
    flat.pop("dense_blocks")
    other, _, _ = T.forward_full(flat, dataclasses.replace(
        SMOKE, n_layers=2, n_dense_layers=0), toks)
    assert float(jnp.abs(other - logits).max()) > 1e-3


@pytest.mark.parametrize("attention", ["mla", "gqa"])
def test_moe_layers_read_their_own_experts(attention, monkeypatch):
    """One dense layer, then three MoE layers with distinct weights: the
    prefill and the decode step, whose grouped matmuls read each layer's
    experts in place from the whole stack by its index within the MoE
    stack, give the logits of the same model with each layer's experts
    sliced out of the stack for it.  Reordering the layers' experts changes
    them, so an index that counts the dense layer, or none, would show."""
    cfg = dataclasses.replace(SMOKE, n_layers=4)
    if attention == "gqa":
        cfg = dataclasses.replace(cfg, use_mla=False, n_kv_heads=2,
                                  d_rope=0, rope_scaling=None)
    params = T.init_params(cfg, jax.random.key(4))
    assert params["blocks"]["moe"]["w_up"].shape[0] == 3
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 10)), jnp.int32)

    def run(params):
        full, cache, _ = T.forward_full(params, cfg, toks[:, :-1],
                                        return_cache=True)
        cache = jax.tree.map(
            lambda c: jnp.pad(c, [(0, 1) if i == 2 else (0, 0)
                                  for i in range(c.ndim)]), cache)
        step, _ = T.forward_decode(params, cfg, toks[:, -1], cache,
                                   jnp.full((2,), 9, jnp.int32))
        return np.asarray(full), np.asarray(step)

    in_place = run(params)
    with monkeypatch.context() as m:      # the scans slice the experts
        m.setattr(T, "_scanned_experts", lambda cfg, blocks: (blocks, None))
        sliced = run(params)
    for got, want in zip(in_place, sliced):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    moe = params["blocks"]["moe"]
    reordered = {**moe, **{n: moe[n][jnp.array([2, 0, 1])]
                           for n in ("w_gate", "w_up", "w_down")}}
    other = run({**params, "blocks": {**params["blocks"], "moe": reordered}})
    for got, moved in zip(in_place, other):
        assert np.abs(got - moved).max() > 1e-3


def test_last_position_head_is_full_heads_row():
    params = T.init_params(SMOKE, jax.random.key(2))
    toks = jnp.asarray(RNG.integers(0, SMOKE.vocab_size, (2, 9)), jnp.int32)
    full, _, _ = T.forward_full(params, SMOKE, toks)
    at = jnp.asarray([8, 4], jnp.int32)
    last, _, _ = T.forward_full(params, SMOKE, toks, head_positions=at)
    assert last.shape == (2, SMOKE.padded_vocab)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(full[0, 8]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last[1]), np.asarray(full[1, 4]),
                               rtol=1e-5, atol=1e-5)


def test_decode_and_insert_donate_the_cache():
    """The cache exists once: each decode step and each insert take the
    engine's cache and give it back updated, the old buffers donated."""
    params = T.init_params(SMOKE, jax.random.key(0))
    eng = ServeEngine(SMOKE, params, max_batch=2, max_len=32)
    eng.submit([1, 2, 3], max_new_tokens=3)
    before = eng.caches["ckv"]
    eng.step()                       # one insert, one decode
    assert before.is_deleted()
    before = eng.caches["ckv"]
    eng.step()
    assert before.is_deleted()


def test_engine_records_and_counters():
    params = T.init_params(SMOKE, jax.random.key(0))
    eng = ServeEngine(SMOKE, params, max_batch=2, max_len=64)
    eng.submit(list(range(1, 12)), max_new_tokens=4)     # bucket 16
    eng.submit([5, 6, 7], max_new_tokens=2, capture=1)   # bucket 8
    done = eng.run_to_completion()
    c = eng.counters
    assert c["prefill_tokens"] == 14
    assert c["prefill_padded_tokens"] == (16 - 11) + (8 - 3)
    assert c["decoded_tokens"] == (4 - 1) + (2 - 1)
    assert c["decode_steps"] == 3
    # every real token routed k times in each MoE layer
    k = SMOKE.experts_per_token
    np.testing.assert_array_equal(eng.expert_counts.sum(axis=1),
                                  [(14 + 4) * k] * SMOKE.n_moe_layers)
    assert [len(r) for r in eng.prefill_records] == [len(PREFILL_FIELDS)] * 2
    assert len(eng.decode_records) == 3
    assert all(len(r) == len(DECODE_FIELDS) for r in eng.decode_records)
    assert [row[1] for row in eng.decode_records] == [2, 1, 1]
    # capture=1: the first token's logits and the first decoded token's
    assert [len(r.logits) for r in done] == [0, 2]
    taken = eng.take_finished()            # in retirement order
    assert sorted(r.rid for r in taken) == [r.rid for r in done]
    assert eng.take_finished() == []
