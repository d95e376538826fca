"""The readers that open up ``phase:fit``, ``phase:evaluate`` and
``preprocess`` (``span_attr``, ``span_self_seconds``,
``idle_under_span``) on hand-made job traces, and their metric files."""

import json
import os

import pytest

from lib import cells


def span(name, start, seconds, children=(), **meta):
    out = {"name": name, "start_ts": start, "duration_s": seconds,
           "children": list(children)}
    if meta:
        out["meta"] = meta
    return out


def build(*roots):
    return {"status": 201, "trace": {"spans": list(roots)}}


def record(builds, device_trace=None):
    return {
        "config": {}, "peaks": None, "builds": builds, "harness": {},
        "counters": {"ready": {}, "window_start": {}, "window_end": {}},
        "device_trace": device_trace, "memory_peak_bytes": 0,
    }


def read(reader, run, **args):
    return cells.load_module("readers", reader).read(run, args)


def fit(owner, start, seconds, children=(), **meta):
    return span(f"train:{owner}", start, seconds,
                [span("phase:fit", start, seconds, children, **meta)])


# --- span_attr ---------------------------------------------------------


def test_span_attr_sums_within_a_build_and_averages_over_builds():
    def one(bytes_dt, bytes_lr, wait):
        return build(span("job:build:t:dt+lr", 10.0, 9.0, [
            fit("dt", 11.0, 4.0, [span("h2d:train", 11.5, 1.0, rows=8, h2d_bytes=bytes_dt)]),
            fit("lr", 11.0, 5.0, [span("h2d:train", 12.0, 1.0, rows=8, h2d_bytes=bytes_lr)],
                lbfgs_iterations=40),
        ], queue_wait_s=wait))
    run = record([one(100, 60, 0.002), one(300, 20, 0.004)])
    assert read("span_attr", run, span="h2d:train", attr="h2d_bytes") == pytest.approx(240.0)
    assert read("span_attr", run, span="h2d:train", under="train:dt",
                attr="h2d_bytes") == pytest.approx(200.0)
    assert read("span_attr", run, prefix="job:", attr="queue_wait_s") == pytest.approx(0.003)
    assert read("span_attr", run, span="phase:fit", under="train:lr",
                attr="lbfgs_iterations") == pytest.approx(40.0)


def test_span_attr_under_means_an_ancestor_not_the_span_itself():
    run = record([build(span("phase:fit", 0.0, 1.0, lbfgs_iterations=7))])
    assert read("span_attr", run, span="phase:fit", attr="lbfgs_iterations") == 7.0
    assert read("span_attr", run, span="phase:fit", under="phase:fit",
                attr="lbfgs_iterations") is None


def test_span_attr_zero_is_a_reading_and_a_missing_attribute_is_not():
    with_zero = record([build(span("job:b", 0.0, 1.0, queue_wait_s=0.0))])
    assert read("span_attr", with_zero, prefix="job:", attr="queue_wait_s") == 0.0
    # the parent commit's trace: the span is there, the attribute is not
    without = record([build(fit("dt", 0.0, 2.0, h2d_bytes=5))])
    assert read("span_attr", without, span="h2d:train", attr="h2d_bytes") is None
    assert read("span_attr", without, span="phase:fit", under="train:dt",
                attr="lbfgs_iterations") is None
    not_a_number = record([build(span("job:b", 0.0, 1.0, queue_wait_s="soon"))])
    assert read("span_attr", not_a_number, prefix="job:", attr="queue_wait_s") is None


# --- span_self_seconds ---------------------------------------------------


def test_self_seconds_take_out_the_union_of_overlapping_children():
    # 10 s; children cover [1, 4] and [3, 6] (union 5 s, not 6) and [8, 9]
    run = record([build(fit("dt", 100.0, 10.0, [
        span("fit:thresholds", 101.0, 3.0),
        span("compile:backend", 103.0, 3.0),
        span("fit:device_wait", 108.0, 1.0),
    ]))])
    assert read("span_self_seconds", run, span="phase:fit") == pytest.approx(4.0)


def test_self_seconds_cut_a_child_to_its_parent():
    # a compile span recorded after the fact may start before the phase
    # and a child may end after it: only the part inside counts
    run = record([build(fit("lr", 100.0, 10.0, [
        span("compile:backend", 98.0, 3.0),
        span("fit:device_wait", 109.0, 5.0),
    ]))])
    assert read("span_self_seconds", run, span="phase:fit") == pytest.approx(8.0)


def test_self_seconds_sum_over_the_spans_and_average_over_builds():
    def one(wait):
        return build(span("job:b", 0.0, 20.0, [
            fit("dt", 1.0, 10.0, [span("fit:device_wait", 2.0, wait)]),
            fit("nb", 1.0, 2.0),
        ]))
    run = record([one(9.0), one(7.0)])
    # (1 + 2) and (3 + 2), averaged
    assert read("span_self_seconds", run, span="phase:fit") == pytest.approx(4.0)
    assert read("span_self_seconds", run, span="phase:fit", under="train:nb") == pytest.approx(2.0)
    # a span with no child is all its own
    assert read("span_self_seconds", run, span="fit:device_wait") == pytest.approx(8.0)


# --- idle_under_span -----------------------------------------------------


def gaps(*intervals):
    return {"window_s": 100.0, "busy_s": 0.0, "modules": {},
            "gaps": sorted(intervals, key=lambda g: g[0] - g[1])}


def test_idle_seconds_inside_the_named_spans():
    run = record(
        [build(span("job:b", 0.0, 100.0, [
            span("preprocess", 10.0, 10.0),
            fit("dt", 30.0, 40.0, [span("fit:thresholds", 30.0, 6.0)]),
            fit("rf", 30.0, 40.0, [span("fit:thresholds", 33.0, 6.0)]),
        ]))],
        # one gap all inside preprocess, one that straddles its end, one
        # across both threshold passes, one inside nothing named
        gaps((11.0, 14.0), (19.0, 22.0), (29.0, 41.0), (80.0, 81.5)),
    )
    assert read("idle_under_span", run, spans=["preprocess"]) == pytest.approx(4.0)
    # the passes overlap: [30, 39] is 9 s of the 12 s gap, not 6 + 6
    assert read("idle_under_span", run, spans=["fit:thresholds"]) == pytest.approx(9.0)
    assert read("idle_under_span", run,
                spans=["preprocess", "fit:thresholds"]) == pytest.approx(13.0)
    # all idle is 19.5 s; 13 of them are inside a named span
    assert read("idle_under_span", run, spans=["preprocess", "fit:thresholds"],
                other=True) == pytest.approx(6.5)


def test_idle_seconds_over_all_the_builds_of_the_window():
    run = record(
        [build(span("preprocess", 10.0, 5.0)), build(span("preprocess", 50.0, 5.0))],
        gaps((12.0, 13.0), (49.0, 52.0)),
    )
    assert read("idle_under_span", run, spans=["preprocess"]) == pytest.approx(3.0)


def test_idle_readers_give_nothing_where_there_is_nothing_to_read():
    traced = [build(span("preprocess", 10.0, 5.0))]
    # a rehearsal has no device trace
    assert read("idle_under_span", record(traced), spans=["preprocess"]) is None
    assert read("idle_under_span", record(traced), spans=["preprocess"], other=True) is None
    # the parent commit has no such span
    run = record(traced, gaps((11.0, 12.0)))
    assert read("idle_under_span", run, spans=["fit:thresholds"]) is None
    # a span that saw no idleness is a reading of 0
    assert read("idle_under_span", record(traced, gaps((30.0, 31.0))),
                spans=["preprocess"]) == 0.0


@pytest.mark.parametrize("reader,args", [
    ("span_attr", {"span": "h2d:train", "attr": "h2d_bytes"}),
    ("span_attr", {"prefix": "job:", "attr": "queue_wait_s"}),
    ("span_self_seconds", {"span": "phase:fit"}),
    ("idle_under_span", {"spans": ["preprocess"]}),
])
def test_builds_without_a_trace_give_nothing(reader, args):
    run = record([{"status": 201}, {"status": 500}], gaps((1.0, 2.0)))
    assert read(reader, run, **args) is None


# --- the metric files ----------------------------------------------------

NEW = {
    "sched.queue_wait_s": "span_attr",
    "build.assemble_s": "span_seconds",
    "build.thresholds_s": "span_seconds",
    "build.standardize_s": "span_seconds",
    "build.fit_enqueue_s": "span_seconds",
    "build.fit_device_wait_s": "span_seconds",
    "build.fit_self_s": "span_self_seconds",
    "build.lr_iterations": "span_attr",
    "build.evaluate_enqueue_s": "span_seconds",
    "build.evaluate_device_wait_s": "span_seconds",
    "build.d2h_s": "span_seconds",
    "build.h2d_train_s": "span_seconds",
    "build.h2d_train_bytes": "span_attr",
    "device_idle_s.preprocess": "idle_under_span",
    "device_idle_s.thresholds": "idle_under_span",
    "device_idle_s.other": "idle_under_span",
    "setup.executable_load_s": "counter_delta",
    "setup.host_peak_bytes": "counter_delta",
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_agrees_with_its_entry(name):
    spec = cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    body = cells.read_json(os.path.join(cells.BENCH_DIR, "metrics", f"{name}.json"))
    assert body["reader"] == NEW[name]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert body[key] == entry[key], key
    assert entry["workloads"] == ["higgs-11m.build5"]
    assert entry["moves"] == ("setup_s" if name.startswith("setup.") else "build_rows_per_s")
    assert (entry["source"] == "device_trace") == name.startswith("device_idle_s.")
    # the reader is there and takes these arguments on an empty run
    assert read(body["reader"], record([]), **body["args"]) is None


def test_a_whole_trace_reads_through_the_metric_files():
    """A build's trace as the program now records it, read by every
    span-sourced metric of this set."""
    lr = fit("lr", 20.0, 30.0, [
        span("fit:standardize", 20.0, 4.0, rows=8, features=2),
        span("h2d:train", 24.0, 1.0, rows=8, h2d_bytes=96),
        span("fit:segment", 25.0, 10.0, iters=25),
        span("fit:segment", 35.0, 10.0, iters=25),
        span("fit:enqueue", 45.0, 3.0),
        span("fit:device_wait", 48.0, 1.5),
    ], lbfgs_iterations=50)
    lr["children"].append(span("phase:evaluate", 50.0, 3.0, [
        span("eval:enqueue", 50.0, 0.125),
        span("eval:device_wait", 50.125, 2.375), span("d2h:predictions", 52.5, 0.25)]))
    dt = fit("dt", 20.0, 20.0, [
        span("fit:thresholds", 20.0, 6.0, rows=8, features=2, bins=32),
        span("h2d:train", 26.0, 1.0, rows=8, h2d_bytes=104),
        span("fit:enqueue", 27.0, 9.0),
        span("fit:device_wait", 36.0, 3.0),
    ])
    run = record(
        [build(span("job:build:t:lr+dt", 10.0, 45.0, [
            span("load_data", 10.0, 1.0),
            span("preprocess", 11.0, 9.0, [span("frame:assemble", 11.5, 8.0, rows=8)]),
            lr, dt,
        ], queue_wait_s=0.004))],
        gaps((11.0, 19.0), (20.5, 24.0), (60.0, 61.0)),
    )
    values = {}
    for name in NEW:
        body = json.load(open(os.path.join(cells.BENCH_DIR, "metrics", f"{name}.json")))
        if body["reader"] != "counter_delta":
            values[name] = read(body["reader"], run, **body["args"])
    assert values == {
        "sched.queue_wait_s": pytest.approx(0.004),
        "build.assemble_s": pytest.approx(8.0),
        "build.thresholds_s": pytest.approx(6.0),
        "build.standardize_s": pytest.approx(4.0),
        "build.fit_enqueue_s": pytest.approx(12.0),
        "build.fit_device_wait_s": pytest.approx(4.5),
        "build.fit_self_s": pytest.approx(0.5 + 1.0),
        "build.lr_iterations": pytest.approx(50.0),
        "build.evaluate_enqueue_s": pytest.approx(0.125),
        "build.evaluate_device_wait_s": pytest.approx(2.375),
        "build.d2h_s": pytest.approx(0.25),
        "build.h2d_train_s": pytest.approx(2.0),
        "build.h2d_train_bytes": pytest.approx(200.0),
        "device_idle_s.preprocess": pytest.approx(8.0),
        "device_idle_s.thresholds": pytest.approx(3.5),
        "device_idle_s.other": pytest.approx(1.0),
    }
