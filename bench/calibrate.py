#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 2]

Runs the cell's loop once per seed in one process (so the program compiles
once), with a short window at the cell's own load, and prints one JSON
line per seed: the numbers that the check compared for the program and,
on the control seeds, for the control (the reference at the next
precision down, put in the program's place).  The last line sums them
up: the largest program reading and the smallest control reading of each
number.  ``limits/<workload>.json`` records what was chosen.  Needs
the chips the cell asks for.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench = harness.benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}[args.workload]
    devices = harness.require_accelerator(wl["chips"])
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    cache: dict = {}
    program: dict[str, float] = {}
    lowest: dict[str, dict[str, float]] = {}
    for seed in seeds:
        t0 = time.perf_counter()
        cell = harness.cell_for(args.workload, seed, args.seconds, False, t0,
                                devices, bench, control=seed in control_seeds,
                                cache=cache)
        result = harness.run_loop(cell)
        print(json.dumps({"seed": seed, "program": result.checks,
                          "control": result.control,
                          "seconds": time.perf_counter() - t0}), flush=True)
        for k, v in result.checks.items():
            program[k] = max(program.get(k, v), v)
        for name, readings in result.control.items():
            low = lowest.setdefault(name, {})
            for k, v in readings.items():
                low[k] = min(low.get(k, v), v)
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "program_max": program, "control_min": lowest}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
