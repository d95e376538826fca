"""The byte model against hand counts, and the readers on a made-up
run record: a reader that finds nothing returns nothing."""

import pytest

from lib import cells
from readers import bytes_model


def test_tree_fit_bytes_by_hand():
    # 8 rows, 3 features, depth 2, one tree: level 0 sees 8 rows, level 1
    # half of them; a row costs 3 bin bytes + 1 label byte + 2 node bytes
    assert bytes_model.levels_of_rows(2) == 1.5
    assert bytes_model.tree_fit_bytes(8, 3, 2, 1) == 1.5 * 8 * (3 + 1 + 2)
    # four trees side by side share the bins and the labels
    assert bytes_model.tree_fit_bytes(8, 3, 2, 4) == 1.5 * 8 * (3 + 1 + 2 * 4)


def test_boosting_bytes_by_hand():
    one_round = 1.5 * 8 * (3 + 1 + 2) + 8 * 8  # plus margin in and out
    assert bytes_model.boosting_bytes(8, 3, 2, 5) == 5 * one_round


def test_binning_bytes_by_hand():
    # 8 rows, 3 features: 4 bytes read and 1 written a cell, once a build
    assert bytes_model.binning_bytes(8, 3) == 8 * 3 * 5


def record(**extra):
    run = {
        "config": {"rows": {"train": 1000}, "features": 4,
                   "hyper": {"max_depth": 5, "gbt_rounds": 20, "rf_trees": 20}},
        "peaks": {"hbm_bytes_per_s": 1e9},
        "builds": [{"status": 201}, {"status": 201}],
        "counters": {"ready": {}, "window_start": {}, "window_end": {}},
        "harness": {}, "device_trace": None, "memory_peak_bytes": 0,
    }
    run.update(extra)
    return run


def read(reader, run, **args):
    return cells.load_module("readers", reader).read(run, args)


def test_roofline_share_from_device_seconds():
    moved = bytes_model.tree_fit_bytes(1000, 4, 5, 1)
    trace = {"window_s": 1.0, "busy_s": 0.25,
             "modules": {"jit__dt_fit": {"seconds": 2 * 10 * moved / 1e9, "count": 2}}}
    run = record(device_trace=trace)
    # two builds share the seconds: each took ten times its floor
    assert read("roofline_share", run, function="_dt_fit", kind="trees") == pytest.approx(10.0)
    assert read("xla_module_seconds", run, function="_dt_fit") == pytest.approx(10 * moved / 1e9)
    assert read("device_idle_share", run) == pytest.approx(75.0)


@pytest.mark.parametrize("reader,args", [
    ("roofline_share", {"function": "_dt_fit", "kind": "trees"}),
    ("xla_module_seconds", {"function": "_dt_fit"}),
    ("device_idle_share", {}),
    ("peak_hbm", {}),
    ("harness_value", {"key": "no_such_clock"}),
    ("span_seconds", {"span": "load_data"}),
    ("span_offset", {"span": "load_data"}),
    ("counter_delta", {"family": "lo_no_such_total", "over": "window"}),
])
def test_nothing_to_read_gives_nothing(reader, args):
    assert read(reader, record(), **args) is None


def test_a_roofline_share_is_never_zero_for_a_program_that_did_not_run():
    trace = {"window_s": 1.0, "busy_s": 0.5, "modules": {}}
    assert read("roofline_share", record(device_trace=trace),
                function="_rf_chunk", kind="trees") is None


def test_span_readers_average_over_builds():
    def build(start, wait, fit):
        return {"status": 201, "start": start, "trace": {"spans": [{
            "name": "job:b", "start_ts": start, "duration_s": 9.0, "children": [
                {"name": "load_data", "start_ts": start + wait, "duration_s": 1.0, "children": []},
                {"name": "train:lr", "start_ts": start + 2, "duration_s": fit, "children": [
                    {"name": "phase:fit", "start_ts": start + 2, "duration_s": fit, "children": []}]},
                {"name": "train:dt", "start_ts": start + 2, "duration_s": 1.0, "children": [
                    {"name": "phase:fit", "start_ts": start + 2, "duration_s": 1.0, "children": []}]},
            ]}]}}
    run = record(builds=[build(100.0, 0.2, 3.0), build(200.0, 0.4, 5.0)])
    assert read("span_offset", run, span="load_data") == pytest.approx(0.3)
    assert read("span_seconds", run, span="phase:fit", under="train:lr") == pytest.approx(4.0)
    assert read("span_seconds", run, span="phase:fit") == pytest.approx(5.0)


def test_parse_metrics_histograms_and_labels():
    from lib.system import parse_metrics

    text = "\n".join([
        "# TYPE lo_x_total counter", 'lo_x_total{a="1"} 2', 'lo_x_total{a="2"} 3',
        "# TYPE lo_h histogram", 'lo_h_bucket{le="0.5"} 1', 'lo_h_bucket{le="+Inf"} 4',
        "lo_h_sum 2.5", "lo_h_count 4", "lo_row_count 7",
    ])
    parsed = parse_metrics(text)
    assert parsed["lo_x_total"] == 5
    assert parsed["lo_h"] == {"buckets": {"0.5": 1.0, "+Inf": 4.0}, "sum": 2.5, "count": 4.0}
    assert parsed["lo_row_count"] == 7
