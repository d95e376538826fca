"""The harness is driven by data: a configuration, a traffic mix, a
per-layer metric and its reader that exist only as newly added files
are found by name, with no edit to a file that was there. And a device
that is not in the table of peaks, or a run without a TPU, fails."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from lib import cells

BENCH_DIR = cells.BENCH_DIR
ROOT = cells.ROOT


@pytest.fixture()
def grown(tmp_path):
    """A copy of the benchmark to which a later PR has added files."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = json.load(open(bench / "configs" / "higgs-11m.json"))
    config["name"] = "susy-5m"
    config["rows"] = {"train": 4_500_000, "test": 500_000}
    config["features"] = 18
    (bench / "configs" / "susy-5m.json").write_text(json.dumps(config))
    mix = json.load(open(bench / "traffic" / "build-loop.json"))
    mix["build"]["clients"] = 2
    (bench / "traffic" / "build-pair.json").write_text(json.dumps(mix))
    (bench / "readers" / "constant.py").write_text(
        "def read(run, args):\n    return args['value']\n"
    )
    (bench / "metrics" / "new.answer.json").write_text(json.dumps({
        "layer": "device", "unit": "s", "better": "lower", "source": "host_clock",
        "moves": "build_rows_per_s",
        "reader": "constant", "args": {"value": 42.0},
    }))
    spec["configs"].append({
        "name": "susy-5m", "source": "https://archive.ics.uci.edu/dataset/279/susy",
        "file": "benchmarks/configs/susy-5m.json", "reduced": [], "why": "test",
    })
    spec["workloads"].append({
        "name": "susy-5m.fast", "config": "susy-5m", "traffic": "build-pair",
        "chips": 1, "why": "test",
    })
    spec["per_layer"].append({
        "name": "new.answer", "unit": "s", "better": "lower", "source": "host_clock",
        "layer": "device", "moves": "build_rows_per_s", "workloads": ["susy-5m.fast"],
    })
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_new_files_are_found_by_name(grown):
    cell = cells.Cell("susy-5m.fast", root=str(grown), bench_dir=str(grown / "benchmarks"))
    assert cell.config["features"] == 18
    assert cell.mix["build"]["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["new.answer"]
    metric = cell.per_layer[0]
    reader = cells.load_module("readers", metric["reader"], str(grown / "benchmarks"))
    assert reader.read({}, metric["args"]) == 42.0
    # a cell lists only the end-to-end metrics that name it, or name none
    assert cell.end_to_end == ["build_rows_per_s", "setup_s"]


def test_the_committed_cells_resolve_and_every_metric_has_its_file():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    end_to_end = {m["name"]: m for m in spec["end_to_end"]}
    for workload in spec["workloads"]:
        cell = cells.Cell(workload["name"])
        assert cell.per_layer and "setup_s" in cell.end_to_end
        for metric in cell.per_layer:
            listed = next(m for m in spec["per_layer"] if m["name"] == metric["name"])
            for key in ("layer", "unit", "better", "source", "moves"):
                assert metric[key] == listed[key], (metric["name"], key)
            assert workload["name"] in end_to_end[metric["moves"]].get(
                "workloads", [workload["name"]]
            )
            cells.load_module("readers", metric["reader"])
    names = {m["name"] for m in spec["per_layer"]}
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "metrics"))}
    assert names <= files  # a metric's file may be there before a cell lists it


def test_unknown_workload_and_unknown_device_kind_fail():
    with pytest.raises(KeyError):
        cells.Cell("no-such.cell")
    cell = cells.Cell("higgs-11m.build5")
    assert cell.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no default"):
        cell.peaks("TPU v9 imaginary")


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "higgs-11m.build5", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 3
    assert done.stdout == ""
    assert "no accelerator" in done.stderr


def test_alone_in_an_empty_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "higgs-11m.build5",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearsal-rows", "2000"],
        env=dict(env, JAX_PLATFORMS="cpu"), cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert done.stdout == ""
