"""``BENCHMARK.json`` against the rules of the benchmark's contract that
can be checked from the file alone, so that a later PR's entries are
refused here and not on the chip."""

import json
import os
import re

from lib import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def spec():
    path = os.path.join(cells.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    return json.load(open(path))


def line(text, limit=200):
    return isinstance(text, str) and 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_and_command():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(s["paths"]) <= 16 and all(PATH.match(p) and not p.startswith("/") for p in s["paths"])
    assert len(s["command"]) <= 32 and all(line(w) for w in s["command"])
    assert isinstance(s["run_seconds"], int) and 1 <= s["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (s["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    s = spec()
    assert 1 <= len(s["configs"]) <= 24
    used = {w["config"] for w in s["workloads"]}
    files = set()
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in s["paths"]) and PATH.match(c["file"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        body = json.load(open(os.path.join(cells.ROOT, c["file"])))
        assert body["reduced"] == c["reduced"] and body["source"] == c["source"]
        assert {"build_201", "precision"} <= set(body["guarantees"])


def test_workloads():
    s = spec()
    assert 1 <= len(s["workloads"]) <= 24
    pairs = set()
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(cells.BENCH_DIR, "traffic", w["traffic"] + ".json"))
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(1, len(s["workloads"]) // 4)
    assert len({w["name"] for w in s["workloads"]}) == len(s["workloads"])


def test_metrics():
    s = spec()
    cell_names = {w["name"] for w in s["workloads"]}
    assert 1 <= len(s["end_to_end"]) <= 16 and 1 <= len(s["per_layer"]) <= 128
    names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    assert len(names) == len(set(names))
    end_to_end = {}
    for m in s["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        end_to_end[m["name"]] = set(m.get("workloads", cell_names))
        assert end_to_end[m["name"]] <= cell_names
    assert end_to_end["setup_s"] == cell_names
    for cell in cell_names:  # set-up, one more end-to-end metric, one per-layer metric
        assert sum(cell in cells_of for cells_of in end_to_end.values()) >= 2
        assert any(cell in m.get("workloads", cell_names) for m in s["per_layer"])
    layers = set()
    for m in s["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and line(m["layer"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", cell_names)) <= end_to_end[m["moves"]]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.add(m["layer"])
    assert len(layers) <= 12


def test_files_under_paths_have_plain_names():
    for folder, _, files in os.walk(cells.BENCH_DIR):
        if "__pycache__" in folder:
            continue
        for name in files:
            assert PATH.match(os.path.relpath(os.path.join(folder, name), cells.ROOT)), name
