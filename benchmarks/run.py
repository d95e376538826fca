#!/usr/bin/env python3
"""One run of one cell: ``python3 benchmarks/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

This process is the one that holds the chip. It starts the seven
services in-process (``services.runner.start_all``, in-memory store,
every ``LO_*`` knob at its default unless the configuration's ``env``
says otherwise), writes the cell's dataset from the seed, warms up with
one whole build, and then drives the services over HTTP on localhost
for the window. Without a TPU it exits with 3 and prints no result.

The last line of standard output is the result. With ``--trace 0`` its
metrics are the cell's end-to-end metrics; with ``--trace 1`` the window
runs under ``jax.profiler`` and the metrics are the per-layer ones.

``--rehearsal-rows N`` is for a CPU box only (benchmarks/README.md): it
cuts the dataset to N rows and marks every metric ``rehearsal.<name>``,
so a number from a CPU run never stands under a device metric's name.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import numpy as np

from lib import cells, correct as correct_lib, traffic

EXIT_NO_DEVICE = 3
# Above this resident set the watcher hands freed heap back every three
# seconds: concurrent compilation and five fits leave more garbage in
# glibc's arenas than a 40 GiB machine has room for (a first run, which
# compiles, peaks at 32 GiB with it).
TRIM_ABOVE_GIB = 24.0


def host_memory_gib() -> tuple[float, float]:
    """This process's resident set now and at its peak, in GiB."""
    values = {}
    with open("/proc/self/status") as handle:
        for line in handle:
            if line.startswith(("VmRSS:", "VmHWM:")):
                key, amount = line.split(":")
                values[key] = int(amount.split()[0]) / 2**20
    return values.get("VmRSS", 0.0), values.get("VmHWM", 0.0)


def watch_host_memory() -> None:
    """A line whenever the resident set has moved by 2 GiB, and the
    heap trimmed while it is large."""

    def watch():
        last = 0.0
        while True:
            now, _ = host_memory_gib()
            if abs(now - last) >= 2.0:
                last = now
                log("host memory")
            if now > TRIM_ABOVE_GIB:
                trim_heap()
                time.sleep(2.0)
            time.sleep(1.0)

    threading.Thread(target=watch, daemon=True).start()


def trim_heap() -> None:
    """Hand freed heap back to the system: compilation and the fits'
    temporaries leave gibibytes that glibc would keep."""
    import ctypes

    ctypes.CDLL("libc.so.6").malloc_trim(0)


def log(message: str) -> None:
    now, peak = host_memory_gib()
    print(
        f"[bench +{time.monotonic() - T0:7.1f}s host {now:4.1f}/{peak:4.1f} GiB] {message}",
        file=sys.stderr, flush=True,
    )


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal-rows", type=int, default=0)
    return parser.parse_args(argv)


def apply_env(cell) -> None:
    """The configuration's ``env`` map into this process's environment,
    before JAX and the program are imported."""
    for key, value in cell.config.get("env", {}).items():
        os.environ[key] = str(value)


def claim_device(cell, rehearsal: bool) -> dict:
    """The device as JAX reports it; refuses anything but enough TPUs."""
    import jax

    devices = jax.devices()
    device = {
        "platform": jax.default_backend(),
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearsal:
        if device["platform"] != "cpu":
            raise SystemExit("--rehearsal-rows is for a CPU box only")
        return device
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(
            f"no accelerator for this cell: JAX reports {device}, "
            f"the cell needs {cell.chips} TPU chip(s)",
            file=sys.stderr,
        )
        sys.stderr.flush()
        os._exit(EXIT_NO_DEVICE)
    return device


def dataset_names(cell) -> dict:
    stem = cell.config["name"].replace("-", "_")
    return {"train": f"{stem}_train", "test": f"{stem}_test"}


def make_data(cell, seed: int, rehearsal_rows: int):
    """The cell's rows from the seed: float32 matrices and labels for the
    train and the test split, and the field names."""
    config = cell.config
    rows = dict(config["rows"])
    if rehearsal_rows:
        share = rows["train"] / (rows["train"] + rows["test"])
        rows = {"train": int(rehearsal_rows * share)}
        rows["test"] = rehearsal_rows - rows["train"]
    maker = cells.load_module("datasets", config["dataset"]["maker"])
    columns, labels, fields = maker.make(
        config["dataset"], seed, rows["train"] + rows["test"]
    )
    return columns, labels, fields, rows


def load_data(system, cell, seed: int, rehearsal_rows: int) -> dict:
    columns, labels, fields, rows = make_data(cell, seed, rehearsal_rows)
    names = dataset_names(cell)
    n = rows["train"]
    for name in names.values():
        system.store.drop(name)
    system.write_dataset(names["train"], [c[:n] for c in columns], labels[:n], fields)
    system.write_dataset(names["test"], [c[n:] for c in columns], labels[n:], fields)
    ref = cells.load_module("reference", "classifiers")
    X = ref.as_matrix(columns, dtype=np.float32)
    return {
        "names": names, "rows": rows, "fields": fields,
        "X_train": X[:n], "y_train": labels[:n],
        "X_test": X[n:], "y_test": labels[n:],
    }


def phase_line(trace: dict) -> str:
    """The build's spans in one line, for the log: stage and seconds."""
    from readers.xplane import iter_spans

    parts = []
    for root in trace["spans"]:
        for span, path in iter_spans(root):
            if len(path) <= 3 and not span["name"].startswith(("job:", "compile:")):
                owner = path[1][6:] + "/" if path[1].startswith("train:") and len(path) == 3 else ""
                parts.append(f"{owner}{span['name']} {span.get('duration_s') or 0:.1f}")
    return ", ".join(parts)


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for device in jax.local_devices()
    ]
    return int(max(peaks))


def run_window(system, cell, data, seconds, trace_dir):
    """The measured window; under the profiler where ``trace_dir``."""
    window = traffic.Window(system, cell, data["names"], traced=bool(trace_dir))
    if not trace_dir:
        window.run(seconds)
        return window
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False  # the programs' HLO is most of a capture
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        from readers import xplane

        with jax.profiler.TraceAnnotation(xplane.MARKER, wall=time.time()):
            pass
        window.run(seconds)
    finally:
        jax.profiler.stop_trace()
    return window


def read_per_layer(cell, run: dict, prefix: str) -> dict:
    metrics = {}
    for metric in cell.per_layer:
        reader = cells.load_module("readers", metric["reader"])
        value = reader.read(run, metric.get("args", {}))
        if value is not None:  # nothing to read: the metric is left out
            metrics[prefix + metric["name"]] = {
                "value": float(value), "unit": metric["unit"]
            }
    return metrics


def judge(system, cell, data, window, seed):
    """``correct``: what the window's last build left behind against the
    plain reference. Runs after the window."""
    ref = cells.load_module("reference", "classifiers")
    comparison = correct_lib.Comparison(
        ref, cell.config, seed,
        data["X_train"], data["y_train"], data["X_test"], data["y_test"],
    )
    finished = [b for b in window.builds if b["status"] == 201]
    if not finished:
        comparison.violations.append("no build finished in the window")
    # every build of a window writes to the same names: what is found was
    # the last one's only if it was written since that one was posted
    outputs, violations = correct_lib.read_build(
        system, ref, cell.config, data["names"], system.models_dir,
        finished[-1]["start"] if finished else window.start,
        data["y_test"], comparison.sample,
    )
    comparison.violations += violations
    comparison.compare(outputs)
    ok, compared = comparison.verdict()
    return ok, compared, comparison, outputs


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = cells.Cell(args.workload)
    apply_env(cell)
    watch_host_memory()
    rehearsal = args.rehearsal_rows > 0
    device = claim_device(cell, rehearsal)
    peaks = None if rehearsal else cell.peaks(device["kind"])
    log(f"device {device}; cell {cell.name}; seed {args.seed}")

    from lib.system import System

    workdir = tempfile.mkdtemp(prefix="lo_bench_")
    harness = {}
    system = System(workdir)
    harness["boot_s"] = time.monotonic() - T0
    log(f"services up, compile cache {system.cache_dir}")
    try:
        started = time.monotonic()
        data = load_data(system, cell, args.seed, args.rehearsal_rows)
        harness["data_s"] = time.monotonic() - started
        log(f"data in the store: {data['rows']} in {harness['data_s']:.1f} s")

        started = time.monotonic()
        status, body = system.build(
            data["names"]["train"], data["names"]["test"],
            cell.config["classifiers"],
            float((cell.mix.get("build") or {}).get("timeout_s", 1100)),
        )
        if status != 201:
            raise RuntimeError(f"warm-up build: {status} {body[:300]!r}")
        harness["first_build_s"] = time.monotonic() - started
        log(f"warm-up build {harness['first_build_s']:.1f} s: " + phase_line(
            system.job_trace(data["names"]["test"], cell.config["classifiers"])
        ))
        # what the warm-up wrote goes: whatever the comparison finds
        # after the window was written in the window
        correct_lib.drop_build(system, cell.config, data["names"], system.models_dir)
        trim_heap()
        setup_s = time.monotonic() - T0
        counters = {"ready": system.counters()}
        counters["window_start"] = counters["ready"]
        log(f"set-up done in {setup_s:.1f} s; window of {args.seconds} s opens")

        trace_dir = os.path.join(workdir, "trace") if args.trace else None
        window = run_window(system, cell, data, args.seconds, trace_dir)
        counters["window_end"] = system.counters()
        peak = memory_peak_bytes()
        attempted, failed = window.attempted_failed()
        harness["build_s"] = window.build_seconds()
        log(
            f"window closed after {window.length_s:.1f} s: {len(window.builds)} "
            f"build(s), {failed} failed; finished in "
            + ", ".join(f"{s:.2f}" for s in harness["build_s"]) + " s"
        )

        prefix = "rehearsal." if rehearsal else ""
        result_device = dict(device, memory_peak_bytes=peak)
        breakdown = None
        if args.trace:
            from readers import xplane

            run = {
                "config": cell.config, "mix": cell.mix, "peaks": peaks,
                "builds": window.builds, "counters": counters, "harness": harness,
                "memory_peak_bytes": peak, "device_trace": None,
            }
            if not rehearsal:
                reduced = xplane.reduce(xplane.find_capture(trace_dir))
                summary = xplane.summarise(reduced, window.start, window.end)
                run["device_trace"] = summary
                result_device["busy_s"] = summary["busy_s"]
                result_device["window_s"] = summary["window_s"]
                traces = [b["trace"] for b in window.builds if "trace" in b]
                ops = sorted(
                    summary["modules"].items(), key=lambda kv: -kv[1]["seconds"]
                )[:10]
                breakdown = {
                    "device_ops": [[name, entry["seconds"]] for name, entry in ops],
                    "idle_gaps": [
                        [xplane.name_gap(gap, traces), gap[1] - gap[0]]
                        for gap in summary["gaps"][:10]
                    ],
                }
            shutil.rmtree(trace_dir, ignore_errors=True)
            metrics = read_per_layer(cell, run, prefix)
        else:
            values = dict(window.end_to_end(data["rows"]["train"]), setup_s=setup_s)
            units = {m["name"]: m["unit"] for m in cell.spec["end_to_end"]}
            metrics = {
                prefix + name: {"value": float(values[name]), "unit": units[name]}
                for name in cell.end_to_end
                if name in values
            }

        started = time.monotonic()
        trim_heap()
        ok, compared, *_ = judge(system, cell, data, window, args.seed)
        log(f"reference and comparison took {time.monotonic() - started:.1f} s")
    finally:
        system.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    result = {
        "correct": bool(ok and failed == 0),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": result_device,
    }
    if rehearsal:
        result["rehearsal"] = True
    if breakdown:
        result["breakdown"] = breakdown
    result["harness"] = harness
    result["compared"] = compared
    for name, entry in compared.items():
        print(f"compared {name}: {entry['value']:.6g} (limit {entry['limit']:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except SystemExit as stop:
        code = stop.code if isinstance(stop.code, int) else 1
        if not isinstance(stop.code, int) and stop.code:
            print(stop.code, file=sys.stderr)
    except BaseException:  # noqa: BLE001 - report, then leave without a result
        import traceback

        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    # the services' daemon threads and JAX's own do not survive an
    # orderly interpreter teardown; everything is flushed, so leave
    os._exit(code)
