#!/usr/bin/env python3
"""Readings for the limits of ``correct``: many seeds in ONE process
after ONE set-up (new rows and one window of one build per seed), the
numbers compared for each, and beside them the control's readings - the
reference put in the program's place and computed in bfloat16 - on the
same seed at the cell's own size.

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        --control 1 --out chiprun_out/readings.jsonl --keep chiprun_out/built

``--keep DIR`` leaves what each build published and stored on the
sampled rows in ``DIR/<seed>.pkl``; ``--from DIR`` reads that back in
place of running the program, so that the reference's side (which is
host numpy wherever it runs) can be read again without the chip.
``--compare 0`` only builds and keeps. ``--faults 1`` adds the readings
of the reference with a fault planted (half the rows, first feature
only, random thresholds, the wrong split). One JSON line per seed goes
to ``--out`` and to standard output. Not part of a benchmark run.
"""

import argparse
import json
import os
import pickle
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run as bench  # noqa: E402
from lib import cells, correct as correct_lib  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", type=int, default=0)
    parser.add_argument("--faults", type=int, default=0)
    parser.add_argument("--compare", type=int, default=1)
    parser.add_argument("--rehearsal-rows", type=int, default=0)
    parser.add_argument("--out", default="")
    parser.add_argument("--keep", default="")
    parser.add_argument("--from", dest="kept", default="")
    args = parser.parse_args()
    cell = cells.Cell(args.workload)
    ref = cells.load_module("reference", "classifiers")
    system = None
    if not args.kept:
        bench.apply_env(cell)
        bench.watch_host_memory()
        bench.claim_device(cell, args.rehearsal_rows > 0)
        from lib.system import System

        system = System(tempfile.mkdtemp(prefix="lo_readings_"))
    for folder in (args.keep, os.path.dirname(args.out)):
        if folder:
            os.makedirs(folder, exist_ok=True)
    for position, seed in enumerate(int(s) for s in args.seeds.split(",")):
        started = time.monotonic()
        line = {"workload": cell.name, "seed": seed}
        if system is None:
            columns, labels, _, rows = bench.make_data(cell, seed, args.rehearsal_rows)
            X, n = ref.as_matrix(columns, dtype="float32"), rows["train"]
            data = {"X_train": X[:n], "y_train": labels[:n],
                    "X_test": X[n:], "y_test": labels[n:]}
            with open(os.path.join(args.kept, f"{seed}.pkl"), "rb") as handle:
                outputs, violations = pickle.load(handle)
        else:
            data = bench.load_data(system, cell, seed, args.rehearsal_rows)
            names = data["names"]
            if position == 0:  # compile and load everything once
                system.build(names["train"], names["test"], cell.config["classifiers"], 1100)
            correct_lib.drop_build(system, cell.config, names, system.models_dir)
            # one build a seed, whatever the cell's mix says of its window
            posted, posted_mono = time.time(), time.monotonic()
            system.build(names["train"], names["test"], cell.config["classifiers"], 1100)
            line["window_s"] = time.monotonic() - posted_mono
            sample = correct_lib.sample_rows(
                seed, len(data["y_test"]), int(cell.config["correct"]["sample_rows"])
            )
            outputs, violations = correct_lib.read_build(
                system, ref, cell.config, names, system.models_dir,
                posted, data["y_test"], sample,
            )
            if args.keep:
                with open(os.path.join(args.keep, f"{seed}.pkl"), "wb") as handle:
                    pickle.dump((outputs, violations), handle)
        line["violations"] = violations
        if args.compare:
            comparison = correct_lib.Comparison(
                ref, cell.config, seed,
                data["X_train"], data["y_train"], data["X_test"], data["y_test"],
            )
            comparison.violations += violations
            comparison.compare(outputs)
            ok, compared = comparison.verdict()
            line["correct"] = ok
            line["numbers"] = {k: v["value"] for k, v in compared.items()}
            line["details"] = comparison.details
            line["reference_s"] = time.monotonic() - started - line.get("window_s", 0.0)
            if args.control:
                before = time.monotonic()
                line["control"] = correct_lib.control_numbers(comparison, outputs)
                line["control_s"] = time.monotonic() - before
            if args.faults:
                line["faults"] = correct_lib.fault_numbers(comparison, outputs)
        line["seconds"] = time.monotonic() - started
        del data
        bench.trim_heap()
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as out:
                out.write(text + "\n")
    if system is not None:
        system.stop()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
