#!/usr/bin/env python3
"""How widely a set of runs of one cell spreads: the table a bound is
read from (PERF.md section 2).

    python3 benchmarks/tools/spread.py <workload> <dir> [<dir> ...]

Each ``<dir>`` is one set: a result line (the last line ``run.py``
printed with ``--trace 0``) in each ``*.json`` file. A spread is
(Q3 - Q1) / median by ``statistics.quantiles(n=4)``, given for all runs
and with the run farthest from the median left out. Beside the windows'
``build_rows_per_s`` stand the one-build readings the same runs hold:
build k of every run, from the result's ``harness.build_s``. Not part of
a benchmark run.
"""

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import cells  # noqa: E402


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def row(label: str, values: list[float]) -> str:
    median = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - median))[:-1]
    return (
        f"{label:28s} n={len(values)} median {median:12.4f}  "
        f"min {min(values):12.4f}  max {max(values):12.4f}  "
        f"spread {100 * spread(values):6.3f} %  "
        f"without the farthest {100 * spread(kept):6.3f} %"
    )


def main(workload: str, *folders: str) -> int:
    rows = cells.Cell(workload).config["rows"]["train"]
    for folder in folders:
        runs = [
            json.loads(open(path).read().strip().splitlines()[-1])
            for path in sorted(glob.glob(os.path.join(folder, "*.json")))
        ]
        wrong = [r for r in runs if not r["correct"] or r["failed"]]
        print(f"{folder}: {len(runs)} runs, {len(wrong)} not correct or with a failed build")
        for name in runs[0]["metrics"]:
            print(row(name, [r["metrics"][name]["value"] for r in runs]))
        builds = [r["harness"]["build_s"] for r in runs]
        for k in range(min(map(len, builds))):
            print(row(f"build {k + 1} alone: seconds", [b[k] for b in builds]))
            print(row(f"build {k + 1} alone: rows/s", [rows / b[k] for b in builds]))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
