#!/usr/bin/env python3
"""Chip benchmark of the ensemble simulator: one run of one cell.

    python3 bench/run.py --workload lotka77.kernel --seed 1 \\
        --seconds 30 --trace 0

Runs from the root of a checkout that holds the simulator (`src/`) and
`BENCHMARK.json`. Warms up the cell's programs, measures a window of
back-to-back ensembles for at least `--seconds`, checks what the window
delivered against the plain reference, and prints one JSON object as
the last line of standard output: the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics from a profiler trace with
`--trace 1`. Exits non-zero, with no result, where JAX finds no TPU or
fewer chips than the cell asks for, or where a traced run reads nothing
for a per-layer metric that BENCHMARK.json lists for the cell.
"""
from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from benchkit import device  # noqa: E402

T_START = device.start_clock()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401  the simulator under test
    except ImportError as e:
        print(f"bench: the simulator is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    from benchkit import harness, spec

    cell = spec.resolve(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except harness.MetricMissing as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
