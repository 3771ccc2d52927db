#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's size.

    python3 bench/control.py --workload energy.sweep_eflfg \
        --seeds 11 12 13 ... --control 3 --seconds 2

For each seed, one process-internal run of the cell's driver as the
benchmark runs it (set-up, warm-up, a short window at the cell's load, the
comparison): the numbers of a sound run, whose largest over a dozen seeds
is the lower reading.  For the first ``--control`` seeds also the control:
the reference itself, computed in bfloat16 (the precision below the
configuration's float32), put in the program's place on the same lanes and
compared with the float64 reference by the same comparison; its smallest
reading is the upper one.  One JSON line per seed and kind on standard
output.  Needs the chips the cell asks for, as ``bench/run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]


def readings(workload: str, seeds, n_control: int, seconds: float,
             overrides=None, root=None):
    """Yield ``{"seed", "kind": "program"|"control", numbers...}``."""
    import ml_dtypes
    from bench import check, harness

    for i, seed in enumerate(seeds):
        _, cell, driver = harness.open_cell(workload, seed, overrides,
                                            root or harness.ROOT)
        driver.setup()
        driver.warm()
        driver.window(seconds)
        driver.release()
        driver.check()
        yield {"seed": seed, "kind": "program", **driver.numbers}
        if i < n_control:
            lanes, prog = driver.sample()
            low = check.reference_lanes(lanes, driver.data, cell.config,
                                        dtype=ml_dtypes.bfloat16)
            yield {"seed": seed, "kind": "control",
                   **check.compare(low, driver.ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    for row in readings(args.workload, args.seeds, args.control,
                        args.seconds):
        row["elapsed_s"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
