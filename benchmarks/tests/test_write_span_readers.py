"""The per-layer metric that reads the store's ``store:append`` spans
(``build.write_copied_bytes``) on hand-made job traces, and its metric
file."""

import os

import pytest

from lib import cells
from test_span_readers import build, read, record, span

CELLS = ["higgs-11m.build5", "epsilon-500k.build3", "expo-onehot-700.build4"]
NAME = "build.write_copied_bytes"


def metric_file():
    return cells.read_json(os.path.join(cells.BENCH_DIR, "metrics", f"{NAME}.json"))


def copied_bytes(run):
    body = metric_file()
    return read(body["reader"], run, **body["args"])


def test_the_metric_file_agrees_with_its_entry():
    spec = cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    body = metric_file()
    assert body["reader"] == "span_attr"
    assert body["args"] == {"span": "store:append", "attr": "copied_bytes"}
    for key in ("layer", "unit", "better", "source", "moves"):
        assert body[key] == entry[key], key
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "store + frame" and entry["unit"] == "bytes"
    assert entry["moves"] == "build_rows_per_s" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert copied_bytes(record([])) is None


def write(owner, start, adopted, copied):
    """One classifier's write-back as the program records it: the drop,
    the rows (one ``store:append`` a batch), the metadata document."""
    return span(f"train:{owner}", start, 4.0, [
        span("write:drop", start, 0.01),
        span("phase:write", start + 0.01, 0.05, [
            span("store:append", start + 0.01, 0.05, rows=100, fields=2003,
                 adopted_bytes=adopted, copied_bytes=copied),
        ], rows=100, fields=2003),
        span("write:metadata", start + 0.06, 0.001),
    ])


def test_copied_bytes_sum_over_a_builds_writes_and_average_over_builds():
    def one(start, copied):
        return build(span("job:build:t:dt+rf+gb", start, 20.0, [
            write(name, start + 10.0 + i, 1_600_000, copied)
            for i, name in enumerate(("dt", "rf", "gb"))
        ]))
    run = record([one(100.0, 2_400), one(200.0, 3_600)])
    # three collections a build: 7,200 and 10,800 bytes, averaged
    assert copied_bytes(run) == pytest.approx(9_000.0)


def test_a_build_that_adopted_everything_reads_zero_not_nothing():
    run = record([build(span("job:build:t:dt", 0.0, 5.0, [write("dt", 1.0, 800, 0)]))])
    assert copied_bytes(run) == 0.0


def test_the_parents_trace_gives_nothing():
    """The parent's program opens no ``store:append`` span: ``phase:write``
    is there, its children are not, and the reader returns nothing."""
    run = record([build(span("job:build:t:dt", 0.0, 5.0, [
        span("train:dt", 1.0, 4.0, [span("phase:write", 2.0, 1.0, rows=100, fields=2003)]),
    ]))])
    assert copied_bytes(run) is None
