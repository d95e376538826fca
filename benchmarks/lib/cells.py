"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A configuration, a traffic mix and a per-layer metric are each one data
file; a reader and a dataset maker are each one module, looked up by the
name a data file gives. Nothing here knows the name of any cell, so a
later PR adds files and entries and edits none.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def read_json(path: str) -> dict:
    with open(path) as handle:
        return json.load(handle)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``benchmarks/<kind>/<name>.py`` as a module."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name: str, root: str = ROOT, bench_dir: str = BENCH_DIR):
        spec = read_json(os.path.join(root, "BENCHMARK.json"))
        self.spec = spec
        self.bench_dir = bench_dir
        matches = [w for w in spec["workloads"] if w["name"] == name]
        if not matches:
            known = ", ".join(w["name"] for w in spec["workloads"])
            raise KeyError(f"unknown workload {name!r} (known: {known})")
        self.workload = matches[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = next(
            c for c in spec["configs"] if c["name"] == self.workload["config"]
        )
        self.config = read_json(os.path.join(root, entry["file"]))
        self.mix = read_json(
            os.path.join(bench_dir, "traffic", f"{self.workload['traffic']}.json")
        )
        self.end_to_end = [
            m["name"]
            for m in spec["end_to_end"]
            if name in m.get("workloads", [name])
        ]
        # a per-layer metric is its own file; BENCHMARK.json says which
        # cells report it
        self.per_layer = []
        for metric in spec["per_layer"]:
            if name not in metric.get("workloads", [name]):
                continue
            path = os.path.join(bench_dir, "metrics", f"{metric['name']}.json")
            self.per_layer.append(read_json(path) | {"name": metric["name"]})

    def peaks(self, device_kind: str) -> dict:
        table = read_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table["devices"]:
            raise KeyError(
                f"device_kind {device_kind!r} is not in benchmarks/peaks.json: "
                "add its peaks with their source, there is no default"
            )
        return table["devices"][device_kind]
