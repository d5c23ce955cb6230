#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

On one set-up, for each seed: a window of the cell's own traffic, then the
compared numbers twice over the same requests, once for the program's
answers and once for the control's (the reference computed in the next
lower precision than the configuration states).  The largest program
reading is a limit's lower end, the smallest control reading its upper
end.  One JSON line per seed, then the two ends.  Runs on the chip only.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench_run._paths()
    from harness import spec

    bench = spec.benchmark()
    c = spec.cell(bench, args.workload)
    device = bench_run.device_of(c["chips"])
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = spec.config(bench, c["config"])
    traffic = spec.traffic(c["traffic"])
    path = spec.path(cfg)
    su = path.set_up(cfg, device)
    lows = {k: 0.0 for k in cfg["limits"]}
    highs = {k: float("inf") for k in cfg["limits"]}
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = path.rows(su, seed)
        r = path.measure(su, c, traffic, pool, seed, args.seconds, False,
                         T_START)
        prog, attempted, _ = path.numbers(r, pool)
        ctl, _, _ = path.numbers(r, pool, control=True)
        for k in cfg["limits"]:
            lows[k] = max(lows[k], prog[k])
            highs[k] = min(highs[k], ctl[k])
        print(json.dumps({"seed": seed, "attempted": attempted,
                          "program": prog, "control": ctl}), flush=True)
    print(json.dumps({"program_max": lows, "control_min": highs}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
