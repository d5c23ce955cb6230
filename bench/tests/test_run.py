"""A whole run on the CPU at test size, through the harness's test-only
entry (``cell.run``; the command itself refuses the CPU): the result
line, the control, and the faults that must make ``correct`` false."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, CPU_AS_V5E, tiny_bench

CONFIGS = ("tiny-bonsai-int8", "tiny-protonn-f32")


@pytest.mark.parametrize("traffic", ["full", "zipf-bursty"])
def test_result_line(run_tiny, traffic):
    # an open-loop mix reports its latencies beside the rate
    report = () if traffic == "full" else ("p50_ms", "served_rps", "setup_s")
    res = run_tiny("tiny-bonsai-int8", traffic, report=report)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    want = {"served_rps", "setup_s"} | (
        {"p50_ms"} if traffic != "full" else set())
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for k, v in res["checks"].items():
        assert v["value"] <= v["limit"], k


@pytest.mark.parametrize("config", CONFIGS)
def test_control_is_not_correct(config, store_in_tmp):
    """The reference in the next lower precision than the configuration
    states fails a limit on the same requests the program passes on."""
    from harness import check, spec
    from paths import fleet

    bench, name = tiny_bench(config, "full")
    c = spec.cell(bench, name)
    cfg = spec.config(bench, c["config"])
    su = fleet.set_up(cfg, CPU_AS_V5E)
    pool = fleet.rows(su, 2**31 + 5)
    r = fleet.measure(su, c, spec.traffic("full"), pool, 2**31 + 5, 0.5,
                      False, time.perf_counter())
    prog, _, _ = fleet.numbers(r, pool)
    ctl, _, _ = fleet.numbers(r, pool, control=True)
    assert check.verdict(prog, cfg["limits"])[0]
    assert not check.verdict(ctl, cfg["limits"])[0]


class _Broken:
    """A tenant's batched forward with a fault planted in its answers."""

    EVERY = 64      # one altered answer in a full bucket of the real cells

    def __init__(self, batched, fault):
        self._batched, self._fault = batched, fault
        self._made = 0

    def __getattr__(self, name):
        return getattr(self._batched, name)

    def __call__(self, **inputs):
        (x,) = inputs.values()
        if self._fault == "half_batch":
            # half of the batch left out: its rows get the other half's
            # answers
            keep = (len(x) + 1) // 2
            out = self._batched(**{k: v[:keep] for k, v in inputs.items()})
            idx = np.arange(len(x)) % keep
            return {k: np.asarray(v)[idx] for k, v in out.items()}
        out = {k: np.array(v) for k, v in self._batched(**inputs).items()}
        # an answer altered where it is produced: the class of one answer
        # in every 64 the tenant gives, whatever the flush's size
        pred = [k for k, v in out.items() if v.dtype.kind == "i"][0]
        scores = [k for k, v in out.items() if v.dtype.kind == "f"][0]
        for i in range(len(x)):
            if (self._made + i) % self.EVERY == 0:
                out[pred][i] = (out[pred][i] + 1) % out[scores].shape[1]
        self._made += len(x)
        return out


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
@pytest.mark.parametrize("config", CONFIGS)
def test_faults_make_correct_false(run_tiny, config, fault):
    def plant(su):
        for t in su.tenants:
            t.state.batched = _Broken(t.state.batched, fault)

    res = run_tiny(config, "full", faults=plant)
    assert res["correct"] is False
    assert res["attempted"] > 0


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "protonn-t1-f32.full",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _command(BENCH.parent)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_only_existing_files():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
