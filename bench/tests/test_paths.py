"""The serving path a configuration names: ``cell.run`` dispatches to
``paths/<name>.py`` and knows no path itself.  A stub path, placed beside
the fleet path for these tests only, is served with no edit to the
harness."""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
import time

import pytest

from conftest import BENCH, CPU_AS_V5E, DATA, tiny_bench


@pytest.fixture
def stub_path(monkeypatch):
    """The stub path found as ``paths/stub.py`` and its traffic mix as
    ``stub-loop``, both from the tests' data."""
    import paths
    from harness import spec

    monkeypatch.setattr(paths, "__path__",
                        [*paths.__path__, str(DATA / "paths")])
    traffic = spec.traffic

    def stub_traffic(name):
        if name == "stub-loop":
            return json.loads((DATA / "traffic" / f"{name}.json")
                              .read_text())
        return traffic(name)

    monkeypatch.setattr(spec, "traffic", stub_traffic)


def _run_stub(faults=None):
    from harness import cell

    bench, name = tiny_bench("tiny-stub", "stub-loop")
    return cell.run(bench, name, 2**31 + 21, 0.2, False, dict(CPU_AS_V5E),
                    time.perf_counter(), faults=faults)


def test_stub_path_runs_through_cell(stub_path):
    res = _run_stub()
    json.dumps(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"served_rps", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert list(res["checks"]) == ["max_rel_err", "unanswered"]


def test_stub_path_fault_makes_correct_false(stub_path):
    def plant(su):
        su.weights = su.weights.copy()
        su.weights[0, 0] += 1.0         # every answer's first score altered

    res = _run_stub(faults=plant)
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > 1e-3


def test_unknown_path_raises(monkeypatch):
    from harness import cell, spec

    bench, name = tiny_bench("tiny-stub", "full")
    config = spec.config

    def unknown(b, n):
        return dict(config(b, n), path="no-such-path")

    monkeypatch.setattr(spec, "config", unknown)
    with pytest.raises(KeyError, match="no-such-path"):
        cell.run(bench, name, 1, 0.2, False, dict(CPU_AS_V5E),
                 time.perf_counter())


def test_unknown_path_no_result(tmp_path):
    """The command resolves the path before set-up and exits 1 with no
    result line."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((DATA / "tiny-stub.json").read_text())
    cfg["path"] = "no-such-path"
    (tmp_path / "bench" / "configs" / "tiny-stub.json").write_text(
        json.dumps(cfg))
    bench["configs"].append({"name": "tiny-stub",
                             "file": "bench/configs/tiny-stub.json"})
    bench["workloads"].append({"name": "tiny-stub.full",
                               "config": "tiny-stub", "traffic": "full",
                               "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny-stub.full",
         "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "no path named 'no-such-path'" in p.stderr
