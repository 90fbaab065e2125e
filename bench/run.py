#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program under ``src/``, on a machine with the chips the cell asks
for.  The last line of standard output is the result as one JSON object;
the numbers that the correctness check compared, each with its limit, are
the last lines of standard error.  Exits non-zero, with no result, when
JAX finds no accelerator or fewer chips than the cell needs.
"""

import sys
import time

T_START = time.perf_counter()

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
