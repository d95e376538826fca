"""The reduction from a profiler capture to numbers, on a small capture
recorded on a TPU v5 lite by ``tools/trace_probe.py`` (three executions
of ``jit_probe_step``, 11.862 us each, 50 ms apart)."""

import os

import pytest

from readers import xplane

CAPTURE = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")
WALL_AT_ZERO = 1790737565.1329842
EXECUTION_S = 11.862e-6


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(CAPTURE)


def test_planes_modules_and_marker(reduced):
    assert list(reduced["devices"]) == ["/device:TPU:0"]
    modules = reduced["devices"]["/device:TPU:0"]["modules"]
    assert list(modules) == ["jit_probe_step"]  # the fingerprint is cut off
    assert len(modules["jit_probe_step"]) == 3
    for start, end in modules["jit_probe_step"]:
        assert end - start == pytest.approx(EXECUTION_S, rel=1e-6)
    assert reduced["wall_at_zero"] == pytest.approx(WALL_AT_ZERO, abs=1e-3)


def test_busy_is_the_union_and_never_more_than_the_modules(reduced):
    plane = reduced["devices"]["/device:TPU:0"]
    busy = sum(b - a for a, b in plane["busy"])
    assert 0 < busy <= 3 * EXECUTION_S * 1.001
    assert all(a < b for a, b in plane["busy"])
    assert all(x[1] <= y[0] for x, y in zip(plane["busy"], plane["busy"][1:]))


def test_summary_over_the_whole_capture(reduced):
    summary = xplane.summarise(reduced, WALL_AT_ZERO, WALL_AT_ZERO + 0.2)
    assert summary["window_s"] == pytest.approx(0.2)
    assert summary["modules"]["jit_probe_step"]["count"] == 3
    assert summary["modules"]["jit_probe_step"]["seconds"] == pytest.approx(
        3 * EXECUTION_S, rel=1e-6
    )
    assert 0 < summary["busy_s"] <= summary["modules"]["jit_probe_step"]["seconds"] * 1.001
    # three executions leave four gaps, the longest first
    lengths = [b - a for a, b in summary["gaps"]]
    assert len(lengths) == 4 and lengths == sorted(lengths, reverse=True)
    assert sum(lengths) + summary["busy_s"] == pytest.approx(0.2, rel=1e-4)


def test_summary_is_cut_to_the_window(reduced):
    # a window that holds only the second execution (at +100.96 ms)
    summary = xplane.summarise(reduced, WALL_AT_ZERO + 0.08, WALL_AT_ZERO + 0.12)
    assert summary["modules"]["jit_probe_step"]["count"] == 1
    assert summary["busy_s"] <= EXECUTION_S * 1.001


def test_no_marker_is_an_error():
    with pytest.raises(ValueError):
        xplane.summarise({"wall_at_zero": None, "devices": {}}, 0.0, 1.0)


def test_gap_is_named_by_the_deepest_program_span():
    trace = {"spans": [{
        "name": "job:build", "start_ts": 0.0, "duration_s": 10.0, "children": [
            {"name": "load_data", "start_ts": 0.0, "duration_s": 2.0, "children": []},
            {"name": "train:dt", "start_ts": 2.0, "duration_s": 8.0, "children": [
                {"name": "phase:fit", "start_ts": 2.0, "duration_s": 5.0, "children": []},
            ]},
        ],
    }]}
    assert xplane.name_gap((0.5, 1.5), [trace]) == "load_data"
    assert xplane.name_gap((3.0, 4.0), [trace]) == "phase:fit[dt]"
    assert xplane.name_gap((8.0, 9.0), [trace]) == "train:dt"
    assert xplane.name_gap((20.0, 21.0), [trace]) == "no span"
