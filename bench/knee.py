#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell to find its knee, on the chip.

    python3 bench/knee.py --workload ccpp.serve_open --seed 5 \
        --seconds 15 --rates 20 40 60 80 100

One process: the cell's set-up and warm-up once, then one window per rate
at that rate, with the cell's traffic otherwise unchanged.  One JSON line
per rate: offered and completed requests per second, p50/p95/max latency,
and the drain (seconds from the window's end to the last result).  The
knee is the highest rate whose completed rate keeps up with the offered
one and whose drain does not grow; the cell offers 0.8 of it
(``rate_hz`` in its traffic file).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]


def sweep(workload: str, seed: int, seconds: float, rates, overrides=None):
    from bench import harness

    _, cell, driver = harness.open_cell(workload, seed, overrides)
    driver.setup()
    driver.warm()
    try:
        for rate in rates:
            cell.traffic["rate_hz"] = float(rate)
            out = driver.window(seconds)
            lats = sorted(x for x in driver.raw["lat"] if x is not None)
            q = lambda p: lats[max(0, math.ceil(p * len(lats)) - 1)]
            yield {"offered_hz": float(rate),
                   "completed_hz": out["metrics"]["req_per_s"],
                   "p50_s": q(0.5), "p95_s": q(0.95), "max_s": lats[-1],
                   "failed": out["failed"],
                   "drain_s": driver.elapsed - seconds}
    finally:
        driver.release()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    for row in sweep(args.workload, args.seed, args.seconds, args.rates):
        row["elapsed_s"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
