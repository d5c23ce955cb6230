"""The benchmark's own tests: run with an explicit path,
``python -m pytest bench/tests``, on the CPU."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

DATA = Path(__file__).resolve().parent / "data"
# A CPU run stands in for the chip only in these tests: the peaks of the
# chip are looked up under its kind, and nothing is reported as a device
# number.
CPU_AS_V5E = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_bench(config: str, traffic: str,
               report: tuple = ()) -> tuple[dict, str]:
    """The benchmark with one more cell: a test-size ``config`` under a mix
    of the benchmark's own, reporting what the mix's cells report and the
    end-to-end metrics named in ``report``."""
    from harness import spec

    bench = spec.benchmark()
    bench["configs"].append({"name": config,
                             "file": f"bench/tests/data/{config}.json"})
    name = f"{config}.{traffic}"
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:        # reported by every cell
            continue
        if any(w.split(".", 1)[1] == traffic for w in m["workloads"]) or (
                m["name"] in report):
            m["workloads"].append(name)
    known = {m["name"] for m in bench["end_to_end"]}
    for metric in report:
        if metric not in known:
            bench["end_to_end"].append({"name": metric, "unit": "ms",
                                        "workloads": [name]})
    return bench, name


@pytest.fixture
def store_in_tmp(tmp_path, monkeypatch):
    """Keep the artifact store of a test run out of the checkout."""
    from harness import spec

    monkeypatch.setattr(spec, "CACHE", tmp_path / "cache")


@pytest.fixture
def run_tiny(store_in_tmp):
    from harness import cell

    def run(config, traffic, seed=2**31 + 12345, seconds=0.5, faults=None,
            report=()):
        bench, name = tiny_bench(config, traffic, report)
        res = cell.run(bench, name, seed, seconds, False, dict(CPU_AS_V5E),
                       time.perf_counter(), faults=faults)
        json.dumps(res)         # the result line is plain JSON
        return res
    return run
