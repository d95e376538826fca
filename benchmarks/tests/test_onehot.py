"""The one-hot categorical deployment, ``expo-onehot-700.build4``: its
maker, its configuration and mix as data files on the harness that was
there, the two per-layer metrics this cell brought, and a rehearsal of
the cell on the CPU - sound, and with the timed path broken underneath."""

import os
import sys

import numpy as np
import pytest

from lib import cells, correct as correct_lib
from test_correct import last_line, over

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "expo-onehot-700.build4"
SEED = 2147483999
# what a test run can hold: seven builds of four classifiers, 700
# columns wide, on the CPU
ROWS = 6600
GROUPS = [
    ("Month", 12), ("DayofMonth", 31), ("DayOfWeek", 7),
    ("UniqueCarrier", 22), ("Origin", 313), ("Dest", 313),
]


@pytest.fixture(scope="module")
def cell():
    return cells.Cell(CELL)


@pytest.fixture(scope="module")
def maker():
    return cells.load_module("datasets", "onehot_categorical")


@pytest.fixture(scope="module")
def made(cell, maker):
    """110,000 rows at the configuration's own width: 100,000 to train."""
    return maker.make(cell.config["dataset"], SEED, 110000)


class TestMaker:
    def test_700_columns_in_the_stated_order(self, cell, maker, made):
        columns, labels, fields = made
        assert maker.group_sizes(cell.config["dataset"]) == GROUPS
        assert len(columns) == len(fields) == cell.config["features"] == 700
        assert len(set(fields)) == 700 and "label" not in fields
        want = [f"{name}_{k}" for name, levels in GROUPS for k in range(1, levels + 1)]
        assert fields == want + ["DepTime", "Distance"]
        assert all(c.dtype == np.float32 and c.shape == (110000,) for c in columns)
        assert labels.dtype == np.int64 and set(np.unique(labels)) == {0, 1}

    def test_one_indicator_a_group_a_row_and_nothing_negative_or_missing(self, made):
        X = np.stack(made[0], axis=1)
        assert np.isfinite(X).all() and (X >= 0).all()
        assert set(np.unique(X[:, :698])) == {0.0, 1.0}
        start = 0
        for _, levels in GROUPS:
            assert (X[:, start : start + levels].sum(axis=1) == 1).all()
            start += levels
        assert start == 698

    def test_the_two_numeric_columns_are_whole_numbers_in_range(self, made):
        dep_time, distance = made[0][698].astype(np.int64), made[0][699].astype(np.int64)
        assert np.array_equal(dep_time, made[0][698]) and np.array_equal(distance, made[0][699])
        assert dep_time.min() >= 0 and dep_time.max() <= 2359 and (dep_time % 100 < 60).all()
        assert distance.min() >= 11 and distance.max() <= 4962
        # right-skewed: a long tail over the mean
        assert np.median(distance) < distance.mean() < np.percentile(distance, 75)

    def test_the_positive_share_is_the_sources(self, cell, made):
        assert cell.config["dataset"]["positive_share"] == 0.19
        assert abs(made[1].mean() - 0.19) < 1e-4

    def test_level_shares_calendar_even_carriers_and_airports_long_tailed(self, made):
        share = np.stack(made[0][:698], axis=1)[:100000].mean(axis=0)
        month, day, weekday = share[:12], share[12:43], share[43:50]
        assert month.min() > 0.06 and month.max() < 0.1
        assert weekday.min() > 0.11 and weekday.max() < 0.17
        assert day[:28].min() > 1 / 40 and day[30] < 0.023  # seven months have a 31st
        carrier, origin, dest = share[50:72], share[72:385], share[385:]
        assert carrier.max() > 0.1 and carrier.min() < 0.02
        for airport in (origin, dest):
            assert 0.04 < airport.max() < 0.09
            # some levels no training row holds, most under 1/32: all their
            # thresholds are 0
            assert (airport == 0).sum() >= 5
            assert (airport < 1 / 32).sum() > 295

    def test_the_destination_depends_on_the_origin_and_sets_the_distance(self, made):
        X = np.stack(made[0], axis=1)
        origin, dest = X[:, 72:385].argmax(axis=1), X[:, 385:698].argmax(axis=1)
        assert (origin != dest).all()
        big = np.bincount(origin).argmax()
        from_big = np.bincount(dest[origin == big], minlength=313) / (origin == big).sum()
        overall = np.bincount(dest, minlength=313) / len(dest)
        assert np.abs(from_big - overall).sum() > 0.2  # total variation over 0.1
        # one pair, one distance, within the 1 % of noise
        pair = origin * 313 + dest
        common = np.bincount(pair).argmax()
        miles = X[pair == common, 699]
        assert len(miles) > 50 and miles.std() < 0.03 * miles.mean()

    def test_the_departure_hour_is_the_strongest_effect(self, made):
        X, labels = np.stack(made[0], axis=1), made[1]
        hour = X[:, 698] // 100
        early, late = labels[(hour >= 5) & (hour < 9)].mean(), labels[(hour >= 17) & (hour < 21)].mean()
        assert late > 2 * early
        share = [labels[X[:, j] == 1].mean() for j in range(50, 72)]
        assert max(share) - min(share) < late - early

    def test_the_seed_decides_shares_effects_and_rows(self, cell, maker):
        dataset = dict(cell.config["dataset"], carriers=6, airports=40)
        columns, labels, fields = maker.make(dataset, SEED, 20000)
        again, labels_again, _ = maker.make(dataset, SEED, 20000)
        assert len(fields) == 12 + 31 + 7 + 6 + 80 + 2
        assert all(a.tobytes() == b.tobytes() for a, b in zip(columns, again))
        assert np.array_equal(labels, labels_again)
        other, other_labels, _ = maker.make(dataset, SEED + 1, 20000)
        assert not np.array_equal(labels, other_labels)
        assert any(not np.array_equal(a, b) for a, b in zip(columns, other))
        # another world, not the same rows in another order: the level
        # shares differ by far more than sampling would move them
        mine = np.array([c.mean() for c in columns[56:96]])
        theirs = np.array([c.mean() for c in other[56:96]])
        assert np.abs(np.sort(mine) - np.sort(theirs)).max() > 0.01
        assert np.abs(mine - theirs).max() > 0.03


class TestConfiguration:
    def test_shapes_are_the_sources_and_rows_the_one_cut(self, cell):
        config = cell.config
        assert config["reduced"] == ["rows"]
        assert config["rows"] == {"train": 524288, "test": 52429}
        assert config["rows"]["test"] == round(config["rows"]["train"] / 10)
        assert config["features"] == 700
        assert config["classifiers"] == ["dt", "rf", "gb", "nb"]
        assert any("524,288" in line and "36 GiB" in line for line in config["assumed"])
        assert any("Logistic regression is left out" in line for line in config["assumed"])
        assert len(config["source"]) <= 200 and "Expo" in config["source"]

    def test_hyper_env_and_guarantees_are_those_of_the_other_cells(self, cell):
        higgs = cells.Cell("higgs-11m.build5").config
        epsilon = cells.Cell("epsilon-500k.build3").config
        config = cell.config
        assert config["hyper"].items() <= higgs["hyper"].items()
        assert set(epsilon["hyper"]) | {"nb_smoothing"} == set(config["hyper"])
        assert config["env"] == higgs["env"] == epsilon["env"]
        assert config["guarantees"] == higgs["guarantees"]  # none weakened
        assert config["correct"] == epsilon["correct"]

    def test_the_twelve_numbers_each_have_a_limit(self, cell):
        numbers = correct_lib.expected_numbers(cell.config["classifiers"])
        assert len(numbers) == 12 and set(numbers) == set(cell.config["limits"])
        assert "lr_prob_gap" not in numbers

    def test_the_window_is_its_own_mix_of_six_builds_on_one_chip(self, cell):
        assert cell.workload["traffic"] == "build-loop-onehot" and cell.chips == 1
        assert cell.mix["build"] == {"clients": 1, "builds": 6, "timeout_s": 1100}

    def test_it_reports_every_metric_but_those_of_lr(self, cell):
        higgs = {m["name"] for m in cells.Cell("higgs-11m.build5").per_layer}
        epsilon = {m["name"] for m in cells.Cell("epsilon-500k.build3").per_layer}
        mine = {m["name"] for m in cell.per_layer}
        assert higgs - mine == {
            "build.fit_s.lr", "device_s.lr", "build.standardize_s", "build.lr_iterations",
        }
        assert mine - epsilon == {"build.fit_s.nb", "device_s.nb"}
        assert "build.distinct_thresholds" in mine & higgs & epsilon
        assert {m for m in mine if m.endswith("_roofline")} == {
            "apply_bins_roofline", "dt_fit_roofline", "rf_chunk_roofline", "gbt_rounds_roofline",
        }
        assert cell.end_to_end == ["build_rows_per_s", "setup_s"]


def thresholds_span(clf, **meta):
    span = {"name": "fit:thresholds", "children": [], "meta": meta}
    fit = {"name": "phase:fit", "children": [span]}
    return {"name": f"train:{clf}", "children": [fit]}


@pytest.mark.parametrize("stamped, value", [
    (True, 834.0),
    # the parent's program stamps no such attribute: nothing is read,
    # nothing raised, and the line leaves the metric out
    (False, None),
])
def test_the_distinct_thresholds_are_read_off_the_one_span_that_ran_the_pass(
    cell, stamped, value
):
    ran = {"rows": 524288, "features": 700, "bins": 32, "passes": 1}
    if stamped:
        ran["distinct_thresholds"] = 834
    waited = {"rows": 524288, "features": 700, "bins": 32, "passes": 0}
    job = {"name": "job:build", "children": [
        thresholds_span("dt", **waited), thresholds_span("rf", **ran),
        thresholds_span("gb", **waited),
    ]}
    run = {"builds": [{"trace": {"spans": [job]}}] * 2}
    spec = next(m for m in cell.per_layer if m["name"] == "build.distinct_thresholds")
    reader = cells.load_module("readers", spec["reader"])
    assert reader.read(run, spec["args"]) == value


@pytest.mark.parametrize("modules, value", [
    ({"jit__fit": {"seconds": 0.5}, "jit__fit_segment_impl": {"seconds": 9.0}}, 0.25),
    ({"jit__fit_segment_impl": {"seconds": 9.0}}, None),  # naive Bayes did not run
])
def test_naive_bayes_device_seconds_are_its_own_module(cell, modules, value):
    spec = next(m for m in cell.per_layer if m["name"] == "device_s.nb")
    reader = cells.load_module("readers", spec["reader"])
    run = {"device_trace": {"modules": modules}, "builds": [{"status": 201}] * 2}
    assert reader.read(run, spec["args"]) == value


# As in the wide cell: the forest's two sampling numbers fall with the
# rows, so a rehearsal a test can hold lies over their limits, sound or
# not (PERF.md section 4 has what they read at 6,000 rows); the limits
# are read at the cell's own size on the chip.
BY_SIZE = {"rf_leaf_z", "rf_split_gap"}


@pytest.mark.parametrize("fault, caught_by", [
    ("none", set()),
    # "first": every node of every tree split on the first column, the
    # indicator of January
    ("poor_splits", {"dt_split_gap", "gb_split_gap"}),
    # columns 28-699 dropped from every fit's histograms
    ("first_block_only", {"dt_split_gap", "gb_split_gap"}),
])
def test_a_rehearsal_of_the_cell_is_sound_and_a_broken_one_is_not(fault, caught_by):
    result = last_line([
        sys.executable, os.path.join(HERE, "onehot_fault_driver.py"), fault, CELL, str(ROWS),
    ])
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["attempted"] == cells.Cell(CELL).mix["build"]["builds"]
    assert set(result["compared"]) - {"violations"} == set(
        correct_lib.expected_numbers(["dt", "rf", "gb", "nb"])
    )
    assert result["compared"]["violations"]["value"] == 0
    if fault == "none":
        assert over(result) <= BY_SIZE
        assert all(name.startswith("rehearsal.") for name in result["metrics"])
    else:
        assert result["correct"] is False and caught_by <= over(result)
