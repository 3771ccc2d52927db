#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload energy.sweep_eflfg --seed 7 \
        --seconds 20 --trace 0

Run from the root of a checkout, on a machine that holds the chips the cell
asks for (``BENCHMARK.json``).  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
last, ``checks``); the numbers that decided ``correct`` are also the last
lines of standard error.  Exits non-zero, with no result line, when JAX finds
no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.monotonic()   # set-up is timed from here

import sys                    # noqa: E402
from pathlib import Path      # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness     # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
