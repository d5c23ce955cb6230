"""The token path (``paths/lm.py``) at test size on the CPU, through
``cell.run``: a tiny DeepSeek-V2-style configuration served by the
program's ``ServeEngine`` under a small closed loop, checked against the
plain reference; planted faults and the float8 control read false."""

from __future__ import annotations

import dataclasses
import json
import time

import numpy as np
import pytest

from conftest import CPU_AS_V5E, DATA, tiny_bench

CONFIG, TRAFFIC = "tiny-dsv2-lite", "tiny-decode"
SEED = 2**31 + 4242


@pytest.fixture
def tiny_traffic(monkeypatch):
    """The tests' small closed loop, found as ``tiny-decode``."""
    from harness import spec

    traffic = spec.traffic

    def find(name):
        if name == TRAFFIC:
            return json.loads((DATA / "traffic" / f"{name}.json").read_text())
        return traffic(name)

    monkeypatch.setattr(spec, "traffic", find)


def _run(faults=None, seconds=0.6):
    from harness import cell

    bench, name = tiny_bench(CONFIG, TRAFFIC)
    return cell.run(bench, name, SEED, seconds, False, dict(CPU_AS_V5E),
                    time.perf_counter(), faults=faults)


def test_lm_path_runs_correct(tiny_traffic):
    res = _run()
    json.dumps(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"served_rps", "setup_s"}
    assert list(res["checks"]) == ["logit_rms_err", "token_miss_share",
                                   "unanswered"]


def _drop_shared_experts(su):
    """The engine's first MoE layer loses its shared experts' output."""
    blocks = su.engine.params["blocks"]
    shared = dict(blocks["moe"]["shared"])
    shared["w_down"] = shared["w_down"].at[0].set(0.0)
    moe = dict(blocks["moe"], shared=shared)
    su.engine.params = dict(su.engine.params, blocks=dict(blocks, moe=moe))


def _renormalise_gates(su):
    """The engine's router renormalises the chosen gates, which the model
    does not."""
    eng = su.engine
    eng.cfg = dataclasses.replace(eng.cfg, norm_topk_prob=True)
    for prog in ("_prefill", "_decode"):
        eng.__dict__.pop(prog, None)
    # warm again outside the window: nothing compiles inside it
    eng.submit([1, 2, 3], max_new_tokens=2)
    while not eng.take_finished():
        eng.step()


@pytest.mark.parametrize("fault", [_drop_shared_experts, _renormalise_gates],
                         ids=["shared_dropped", "gates_renormalised"])
def test_lm_faults_make_correct_false(tiny_traffic, fault):
    res = _run(faults=fault)
    assert res["correct"] is False
    assert res["checks"]["logit_rms_err"]["value"] > (
        res["checks"]["logit_rms_err"]["limit"])


def test_lm_control_is_not_correct(tiny_traffic, store_in_tmp):
    from harness import check, spec
    from paths import lm

    bench, name = tiny_bench(CONFIG, TRAFFIC)
    c = spec.cell(bench, name)
    cfg = spec.config(bench, c["config"])
    su = lm.set_up(cfg, CPU_AS_V5E)
    pool = lm.rows(su, SEED)
    r = lm.measure(su, c, spec.traffic(TRAFFIC), pool, SEED, 0.6, False,
                   time.perf_counter())
    prog, attempted, _ = lm.numbers(r, pool)
    ctl, _, _ = lm.numbers(r, pool, control=True)
    assert check.verdict(prog, cfg["limits"])[0]
    assert not check.verdict(ctl, cfg["limits"])[0]
    # the readers that need no device trace read this run; the rooflines,
    # which do, read nothing
    for m in ("decode_step_ms", "prefill_ms", "ttft_ms", "itl_p99_ms",
              "prefill_share_pct", "expert_load_max", "lm_mfu"):
        v = spec.reader(m)(r)
        assert v is not None and np.isfinite(v) and v > 0, m
    assert 1.0 <= spec.reader("expert_load_max")(r) <= r.expert_counts.shape[1]
    assert spec.reader("decode_roofline")(r) is None
    assert spec.reader("prefill_roofline")(r) is None
    # counters are read at the window's marks, records kept by their start
    assert abs(r.counters["decode_steps"] - len(r.decodes["t0"])) <= 1
