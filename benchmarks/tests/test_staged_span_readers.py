"""The per-layer metrics that read the spans of the H2D copy
(``h2d:stage``, ``h2d:put``, ``h2d:land``), the device cache
(``devcache:lookup``, ``devcache:digest``) and the wait in the queue
(``sched:queue``), on hand-made job traces, and their metric files."""

import os

import pytest

from lib import cells
from test_span_readers import build, fit, gaps, read, record, span

# --- the spans of the H2D copy, the device cache and the queue -----------

CELLS = ["higgs-11m.build5", "epsilon-500k.build3", "expo-onehot-700.build4"]

STAGED = {
    "build.h2d_train_stage_s": "span_seconds",
    "build.h2d_train_land_s": "span_seconds",
    "build.eval_digest_s": "span_seconds",
    "build.devcache_misses": "span_attr",
    "build.devcache_evicted_bytes": "span_attr",
    "sched.queue_behind_s": "span_attr",
    "device_idle_s.sched_queue": "idle_under_span",
    "device_idle_s.devcache": "idle_under_span",
}


def staged_values(run):
    values = {}
    for name in STAGED:
        body = cells.read_json(os.path.join(cells.BENCH_DIR, "metrics", f"{name}.json"))
        values[name] = read(body["reader"], run, **body["args"])
    return values


@pytest.mark.parametrize("name", sorted(STAGED))
def test_staged_metric_file_agrees_with_its_entry(name):
    spec = cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    body = cells.read_json(os.path.join(cells.BENCH_DIR, "metrics", f"{name}.json"))
    assert body["reader"] == STAGED[name]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert body[key] == entry[key], key
    assert entry["workloads"] == CELLS
    assert entry["moves"] == "build_rows_per_s" and entry["better"] == "lower"
    assert (entry["source"] == "device_trace") == name.startswith("device_idle_s.")
    assert read(body["reader"], record([]), **body["args"]) is None


def lookup(kind, start, seconds, hit, nbytes, evicted_bytes=0, children=()):
    return span("devcache:lookup", start, seconds, children, kind=kind,
                hits=int(hit), misses=int(not hit), bytes=nbytes,
                evicted=int(evicted_bytes > 0), evicted_bytes=evicted_bytes)


def staged_build(start, evicted_bytes, digest_s, hit):
    """One build as the program records it: the wait in the queue, the
    table lookups of ``load_data``, the training copy's stages, and the
    test split's content lookup under ``phase:evaluate``."""
    t = start
    evaluate_children = [
        lookup("devmat", t + 20.0, digest_s + (0.0 if hit else 2.0), hit, 920,
               evicted_bytes, [span("devcache:digest", t + 20.0, digest_s, bytes=1600)]
               + ([] if hit else [span("h2d:matrix", t + 20.0 + digest_s, 2.0)])),
        lookup("devlab", t + 23.0, 0.5, True, 40,
               children=[span("devcache:digest", t + 23.0, 0.25, bytes=8)]),
    ]
    return build(span("job:build:t:dt", t, 30.0, [
        span("sched:queue", t - 0.5, 0.5, behind_s=0.25, behind=1, behind_kinds=["warmup"]),
        span("load_data", t, 1.0, [
            lookup("table", t, 0.5, True, 1950),
            lookup("table", t + 0.5, 0.5, True, 160),
        ]),
        fit("dt", t + 2.0, 10.0, [
            span("h2d:train", t + 2.0, 2.0, [
                span("h2d:stage", t + 2.0, 1.0, bytes=960),
                span("h2d:put", t + 3.0, 0.25),
                span("h2d:stage", t + 3.25, 0.125, bytes=40),
                span("h2d:put", t + 3.375, 0.125),
                span("h2d:land", t + 3.5, 0.5),
            ], rows=8, h2d_bytes=1000, copies=1),
        ]),
        span("train:dt", t + 12.0, 14.0, [span("phase:evaluate", t + 20.0, 6.0, evaluate_children)]),
    ], queue_wait_s=0.5))


def test_the_staged_metrics_read_a_whole_trace():
    run = record(
        [staged_build(100.0, 1_000, 1.0, hit=False), staged_build(200.0, 0, 0.5, hit=True)],
        # idle in the first build's queue wait and its lookup's copy, in
        # the second's digest
        gaps((99.6, 100.0), (121.5, 122.5), (220.0, 220.25), (290.0, 291.0)),
    )
    assert staged_values(run) == {
        # each build: 1.0 + 0.125 of staging, 0.5 of landing; averaged
        "build.h2d_train_stage_s": pytest.approx(1.125),
        "build.h2d_train_land_s": pytest.approx(0.5),
        # both digests of a build, averaged: (1.25 + 0.75) / 2
        "build.eval_digest_s": pytest.approx(1.0),
        # the first build's devmat missed; the second's all hit
        "build.devcache_misses": pytest.approx(0.5),
        "build.devcache_evicted_bytes": pytest.approx(500.0),
        "sched.queue_behind_s": pytest.approx(0.25),
        # idle_under_span sums over the window's builds
        "device_idle_s.sched_queue": pytest.approx(0.4),
        "device_idle_s.devcache": pytest.approx(1.0 + 0.25),
    }


def test_a_build_of_all_hits_reads_zero_misses_not_nothing():
    run = record([staged_build(100.0, 0, 0.5, hit=True)], gaps((290.0, 291.0)))
    values = staged_values(run)
    assert values["build.devcache_misses"] == 0.0
    assert values["build.devcache_evicted_bytes"] == 0.0
    assert values["device_idle_s.devcache"] == 0.0


def test_the_parents_trace_gives_the_staged_metrics_nothing():
    """The parent's program opens none of these spans: every reader
    returns nothing, and raises nothing."""
    run = record(
        [build(span("job:build:t:dt", 10.0, 9.0, [
            span("load_data", 10.0, 1.0),
            fit("dt", 11.0, 4.0, [span("h2d:train", 11.5, 1.0, rows=8, h2d_bytes=8)]),
        ], queue_wait_s=0.3))],
        gaps((12.0, 13.0)),
    )
    assert staged_values(run) == {name: None for name in STAGED}
