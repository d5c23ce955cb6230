"""Cells, configurations, serving paths, traffic mixes and metrics, found
by name.

Nothing here knows a cell: everything comes from ``BENCHMARK.json`` and the
files it names, so a configuration, a serving path, a traffic mix or a
metric is added by adding its file and its entry.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]      # the checkout
BENCH = ROOT / "bench"
CACHE = ROOT / ".bench_cache"                    # compile cache and store


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell(bench: dict, name: str) -> dict:
    return _entry(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    cfg = json.loads((ROOT / _entry(bench["configs"], name, "config")["file"])
                     .read_text())
    if cfg.get("name") != name:
        raise ValueError(f"config file of {name!r} names {cfg.get('name')!r}")
    return cfg


def path(cfg: dict):
    """The module of the serving path the configuration names under
    ``"path"`` (``fleet`` where it names none): ``paths/<name>.py``, as
    ``paths/__init__.py`` sets out.  An unknown path raises ``KeyError``."""
    import paths

    name = cfg.get("path", "fleet")
    if not any((Path(d) / f"{name}.py").is_file() for d in paths.__path__):
        raise KeyError(f"no path named {name!r}")
    return importlib.import_module(f"paths.{name}")


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def metrics(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run: the end-to-end
    ones without a trace, the per-layer ones with it.  A per-layer metric
    lists the cells that report it under ``workloads``; an end-to-end
    metric does so where only some cells report it, and is reported by
    every cell where it has no such list (``setup_s``)."""
    if trace:
        return [m for m in bench["per_layer"] if cell_name in m["workloads"]]
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
