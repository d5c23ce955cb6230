#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell names a configuration and a traffic
mix in ``BENCHMARK.json``; the harness finds their files, and the serving
path the configuration names (``bench/paths/``), by name; an unknown cell
or path exits 1 before set-up.  Set-up is timed as ``setup_s``: on the fleet
path, JAX's start, the tenants' compiles through the artifact store, and
every batch size of every tenant served once, which compiles each tenant's
forward at each bucket (the batch lane pads and slices on the host).  Then
the traffic runs for ``--seconds``; then every answer is checked against
the plain reference.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones), ``device``, with a trace ``breakdown``,
and last ``checks``, each compared number beside its limit.

With no TPU, or fewer chips than the cell asks for, it exits 1 and prints
no result: there is no CPU fallback.  JAX's compilation cache and the
artifact store live in ``.bench_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _paths() -> None:
    """Put the program and the benchmark on the import path, and JAX's
    compilation cache at its fixed place in the checkout (before JAX is
    imported, which reads it)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".bench_cache" /
                                                  "jax")
    for p in (ROOT / "bench", ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_of(chips: int) -> dict:
    """The chips the run is on; raises unless JAX finds at least ``chips``
    TPU devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's first device is {devs[0]!r}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX finds "
                           f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def main(argv=None) -> int:
    args = parse(argv)
    _paths()
    try:
        from harness import cell, spec

        bench = spec.benchmark()
        c = spec.cell(bench, args.workload)
        spec.path(spec.config(bench, c["config"]))
        device = device_of(c["chips"])
    except (RuntimeError, KeyError, ImportError, OSError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = cell.run(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), device, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
