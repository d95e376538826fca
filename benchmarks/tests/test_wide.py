"""The wide dense deployment, ``epsilon-500k.build3``: its maker, its
configuration and mix as data files on the harness that was there, the
two per-layer metrics that read the block plan off the ``fit:enqueue``
spans, and a rehearsal of the cell on the CPU - sound, and with the
timed path broken underneath."""

import os
import sys

import numpy as np
import pytest

from lib import cells, correct as correct_lib
from test_correct import last_line, over

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "epsilon-500k.build3"
# what a test run can hold: every build is 2,000 columns wide on the CPU
ROWS = 3000


@pytest.fixture(scope="module")
def cell():
    return cells.Cell(CELL)


@pytest.fixture(scope="module")
def maker():
    return cells.load_module("datasets", "wide_dense")


@pytest.fixture(scope="module")
def made(cell, maker):
    """60,000 rows at the configuration's own width."""
    return maker.make(cell.config["dataset"], 2147483777, 60000)


class TestMaker:
    @pytest.mark.parametrize("threads", [1, 3])
    def test_the_seed_decides_the_rows_and_the_threads_do_not(
        self, cell, maker, made, monkeypatch, threads
    ):
        dataset = dict(cell.config["dataset"], features=160)
        monkeypatch.setattr(maker, "THREADS", 8)
        columns, labels, fields = maker.make(dataset, 2147483777, 5000)
        monkeypatch.setattr(maker, "THREADS", threads)
        again, labels_again, _ = maker.make(dataset, 2147483777, 5000)
        assert all(a.tobytes() == b.tobytes() for a, b in zip(columns, again))
        assert np.array_equal(labels, labels_again)
        other, other_labels, _ = maker.make(dataset, 2147483778, 5000)
        assert not np.array_equal(columns[0], other[0])
        assert not np.array_equal(labels, other_labels)
        assert fields == [f"f{j}" for j in range(160)]

    def test_shape_dtype_and_balance_are_the_sources(self, cell, made):
        columns, labels, fields = made
        assert len(columns) == len(fields) == cell.config["features"] == 2000
        assert all(c.dtype == np.float32 and c.shape == (60000,) for c in columns)
        assert labels.dtype == np.int64 and abs(labels.mean() - 0.5) < 1e-3

    def test_columns_are_standardised_and_rows_of_unit_length(self, made):
        X = np.stack(made[0], axis=1).astype(np.float64)
        assert np.allclose((X**2).sum(axis=1), 1.0, atol=1e-5)
        # row scaling comes second, so a column's moments are the
        # standardised ones to within the spread of the row norms
        width = X.shape[1]
        assert np.abs(X.mean(axis=0)).max() * np.sqrt(width) < 0.02
        assert np.abs(X.std(axis=0) * np.sqrt(width) - 1.0).max() < 0.05
        # continuous columns: a quantile threshold for every bin edge
        assert min(len(np.unique(column)) for column in made[0][:50]) > 59000
        # and correlated ones: columns that share a latent factor
        corr = np.corrcoef(X[:, :200], rowvar=False)
        assert np.abs(corr - np.eye(200)).max() > 0.1

    def test_every_block_of_80_holds_columns_that_decide_the_label(
        self, cell, maker, made
    ):
        dataset = cell.config["dataset"]
        columns, labels, _ = made
        informative, weights = maker.informative_columns(dataset, 2147483777)
        blocks = informative // dataset["block"]
        assert np.array_equal(
            np.bincount(blocks), np.full(25, dataset["informative_per_block"])
        )
        centred = labels - labels.mean()
        corr = np.array([
            abs(np.dot(centred, c - c.mean()))
            / (np.linalg.norm(centred) * np.linalg.norm(c - c.mean()))
            for c in columns
        ])
        noise = 3.0 / np.sqrt(len(labels))  # three standard errors of no correlation
        for block in range(25):  # its strongest informative column shows
            assert corr[informative[blocks == block]].max() > noise, block
        # the other columns share latent factors with them and carry a
        # weaker echo of the score
        rest = np.setdiff1d(np.arange(len(columns)), informative)
        assert np.median(corr[informative]) > 2 * np.median(corr[rest])


class TestConfiguration:
    def test_shapes_are_the_sources_and_rows_the_one_cut(self, cell):
        config = cell.config
        assert config["reduced"] == ["rows"]
        assert config["rows"] == {"train": 163840, "test": 100000}
        assert config["rows"]["train"] == 131072 * 5 // 4  # its own bucket
        assert config["features"] == config["dataset"]["features"] == 2000
        assert config["dataset"]["positive_share"] == 0.5
        assert config["classifiers"] == ["dt", "rf", "gb"]
        assert any("163,840" in line and "40 GiB" in line for line in config["assumed"])

    def test_hyper_env_and_guarantees_are_those_of_higgs(self, cell):
        higgs = cells.Cell("higgs-11m.build5").config
        config = cell.config
        assert config["hyper"].items() <= higgs["hyper"].items()
        assert {"max_depth", "max_bins", "rf_trees", "gbt_rounds", "gbt_step",
                "gbt_lambda", "gbt_hessian_floor"} <= set(config["hyper"])
        assert config["env"] == higgs["env"]
        assert config["guarantees"] == higgs["guarantees"]  # none weakened

    def test_the_nine_tree_numbers_each_have_a_limit(self, cell):
        config = cell.config
        numbers = correct_lib.expected_numbers(config["classifiers"])
        assert len(numbers) == 9 and set(numbers) == set(config["limits"])
        options = config["correct"]
        assert options["rf_trees_checked"] >= 3 and options["gbt_rounds_first"] >= 2
        assert options["gbt_rounds_drawn"] >= 1

    def test_the_window_is_its_own_mix_and_holds_three_builds_or_more(self, cell):
        assert cell.workload["traffic"] == "build-loop-wide" and cell.chips == 1
        assert cell.mix["build"]["clients"] == 1 and cell.mix["build"]["builds"] >= 3

    def test_it_reports_every_metric_but_those_of_lr_and_nb(self, cell):
        higgs = {m["name"] for m in cells.Cell("higgs-11m.build5").per_layer}
        mine = {m["name"] for m in cell.per_layer}
        assert higgs - mine == {
            "build.fit_s.lr", "build.fit_s.nb", "device_s.lr", "device_s.nb",
            "build.standardize_s", "build.lr_iterations", "build.lr_matrix_passes",
            "device_s.lr_standardize",
        }
        assert {"build.hist_blocks", "build.hist_indicator_bytes"} <= mine & higgs
        assert cell.end_to_end == ["build_rows_per_s", "setup_s"]


def enqueue(clf, **meta):
    span = {"name": "fit:enqueue", "children": []}
    if meta:
        span["meta"] = meta
    fit = {"name": "phase:fit", "children": [span]}
    return {"name": f"train:{clf}", "children": [fit]}


@pytest.mark.parametrize("metric, stamped, value", [
    ("build.hist_blocks", True, 75.0),
    ("build.hist_indicator_bytes", True, 1677721600.0),
    # the parent's program stamps nothing: nothing is read, nothing raised
    ("build.hist_blocks", False, None),
    ("build.hist_indicator_bytes", False, None),
])
def test_the_block_plan_is_read_off_the_enqueue_spans(cell, metric, stamped, value):
    plan = {"hist_block_features": 80, "hist_blocks": 25,
            "hist_indicator_bytes": 1677721600} if stamped else {}
    job = {"name": "job:build", "children": [
        enqueue("dt", **plan), enqueue("rf", subset_k=45, **plan), enqueue("gb", **plan),
    ]}
    if not stamped:
        del job["children"][1]["children"][0]["children"][0]["meta"]
    run = {"builds": [{"trace": {"spans": [job]}}] * 2}
    spec = next(m for m in cell.per_layer if m["name"] == metric)
    reader = cells.load_module("readers", spec["reader"])
    assert reader.read(run, spec["args"]) == value


# A forest's leaves and thresholds carry its own bootstrap and its own
# choice among a node's 1,395 candidates, and the two numbers that hold
# them to all rows fall with the rows: the reference's own float64 forest
# reads rf_leaf_z 0.94 / 0.94 / 0.60 and rf_split_gap 0.053 / 0.034 /
# 0.020 at 6,000 / 20,000 / 40,000 training rows, and 0.28-0.55 and
# 0.010-0.014 at the cell's 163,840. Their limits are read at the cell's
# size on the chip (PERF.md section 4); a rehearsal a test can hold lies
# over them, sound or not, and is held to the other seven.
BY_SIZE = {"rf_leaf_z", "rf_split_gap"}


@pytest.mark.parametrize("fault, caught_by", [
    ("none", set()),
    # every node of every tree split on the first column's median (the
    # floor ensemble_loss_gap cannot show it here: at 1,862 rows the
    # reference's own tree is no better than chance on the test rows)
    ("poor_splits", {"dt_split_gap", "gb_split_gap", "dt_loss_gap"}),
    # columns 80-1,999 dropped from every fit's histograms
    ("first_block_only", {"dt_split_gap", "gb_split_gap"}),
])
def test_a_rehearsal_of_the_cell_is_sound_and_a_broken_one_is_not(fault, caught_by):
    result = last_line([
        sys.executable, os.path.join(HERE, "wide_fault_driver.py"), fault, CELL, str(ROWS),
    ])
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["attempted"] == cells.Cell(CELL).mix["build"]["builds"]
    assert set(result["compared"]) - {"violations"} == set(
        correct_lib.expected_numbers(["dt", "rf", "gb"])
    )
    assert result["compared"]["violations"]["value"] == 0
    if fault == "none":
        assert over(result) <= BY_SIZE
        assert all(name.startswith("rehearsal.") for name in result["metrics"])
    else:
        assert result["correct"] is False and caught_by <= over(result)
