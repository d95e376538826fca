"""The window rule of ``lib/traffic.py`` on a stub system: no chip, no
build. A client stops once ``--seconds`` have passed and it has finished
the mix's ``builds``; the window ends at the last 201; a traced window
holds one build; a rate is all finished builds' rows over all of it."""

import time
import types

import pytest

from lib import traffic

BUILD_S = 0.05
ROWS = 1000


class StubSystem:
    """Answers ``POST /models`` after ``BUILD_S`` seconds: 201, or 500
    from the ``fails_at``-th build on."""

    def __init__(self, fails_at=None):
        self.posted = 0
        self.fails_at = fails_at

    def build(self, train, test, classifiers, timeout):
        self.posted += 1
        time.sleep(BUILD_S)
        if self.fails_at and self.posted >= self.fails_at:
            return 500, b"planted"
        return 201, b"created"

    def counters(self):
        return {"posted": self.posted}

    def job_trace(self, test, classifiers):
        return {"spans": []}


def window(build: dict, system=None, traced=False) -> traffic.Window:
    cell = types.SimpleNamespace(mix={"build": build}, config={"classifiers": ["lr"]})
    names = {"train": "a_train", "test": "a_test"}
    return traffic.Window(system or StubSystem(), cell, names, traced=traced)


@pytest.mark.parametrize("build,seconds,traced,expected", [
    ({"builds": 3}, 0.0, False, 3),
    ({"builds": 3}, 10 * BUILD_S, False, None),  # --seconds outlasts three builds
    ({}, 0.0, False, 1),  # a mix that names no count: one build, as before
    ({"clients": 1, "timeout_s": 5}, 0.6 * BUILD_S, False, 1),
    ({"builds": 3}, 0.0, True, 1),  # a traced window holds one build
    ({"builds": 2, "clients": 2}, 0.0, False, 4),  # each client its own count
])
def test_how_many_builds_a_window_holds(build, seconds, traced, expected):
    w = window(build, traced=traced)
    w.run(seconds)
    attempted, failed = w.attempted_failed()
    assert failed == 0 and attempted == len(w.builds) == len(w.build_seconds())
    ends = sorted(b["end_mono"] - w.start_mono for b in w.builds)
    if expected is None:
        # builds go on until the clock has run out, and the one in flight
        # then is the last
        assert attempted > 3
        assert ends[-1] >= seconds and all(end < seconds for end in ends[:-1])
    else:
        assert attempted == expected
    # the window ends at the last 201, not where the clock stopped
    assert w.length_s == pytest.approx(ends[-1], abs=0.02)
    assert all(("trace" in b) == traced for b in w.builds)


def test_the_rate_is_all_finished_builds_rows_over_the_whole_window():
    w = window({"builds": 3})
    w.run(0.0)
    seconds = w.build_seconds()
    assert len(seconds) == 3 and all(s >= BUILD_S for s in seconds)
    span = w.builds[-1]["end_mono"] - w.start_mono
    assert span >= sum(seconds)  # back to back: nothing between them is left out
    assert w.end_to_end(ROWS) == {"build_rows_per_s": 3 * ROWS / span}


def test_a_build_that_fails_ends_its_client():
    w = window({"builds": 3}, StubSystem(fails_at=2))
    w.run(0.0)
    assert w.attempted_failed() == (2, 1)
    assert [b["status"] for b in w.builds] == [201, 500]
    assert len(w.build_seconds()) == 1
    # the rate counts what finished, up to its 201
    span = w.builds[0]["end_mono"] - w.start_mono
    assert w.end_to_end(ROWS) == {"build_rows_per_s": ROWS / span}
    none = window({"builds": 3}, StubSystem(fails_at=1))
    none.run(0.0)
    assert none.attempted_failed() == (1, 1) and none.end_to_end(ROWS) == {}
