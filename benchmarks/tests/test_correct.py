"""``correct`` has been shown to fail: the control reads over its
limits, and a run whose timed path is broken underneath comes out as not
correct. The sizes are what a test run can hold (CPU, tens of thousands
of rows); the readings at the cell's own size on the chip are in
PERF.md."""

import json
import os
import subprocess
import sys

import pytest

from lib import cells

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BUILD = "higgs-11m.build5"
ROWS = 60000
# a forest's leaves are held to the bootstrap's own noise, and under some
# thousands of rows a leaf the tree's own choice of splits shows in it:
# the sound run is the one test that needs more rows
SOUND_ROWS = 300000


def last_line(command):
    done = subprocess.run(
        command, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def drive(fault, rows=ROWS):
    return last_line(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), fault, BUILD, str(rows)]
    )


def over(result):
    return {
        name for name, entry in result["compared"].items()
        if entry["value"] > entry["limit"]
    }


def test_a_sound_run_is_correct():
    result = drive("none", SOUND_ROWS)
    assert result["correct"] is True and result["failed"] == 0
    assert over(result) == set()
    assert list(result)[-1] == "compared"  # the numbers come last in the line
    assert all(name.startswith("rehearsal.") for name in result["metrics"])
    # the window held the builds its mix asks for, each with its seconds
    builds = cells.Cell(BUILD).mix["build"]["builds"]
    assert result["attempted"] == builds == len(result["harness"]["build_s"])


def readings(*arguments):
    return last_line([
        sys.executable, os.path.join(ROOT, "benchmarks", "tools", "readings.py"),
        "--workload", BUILD, "--rehearsal-rows", str(ROWS), *arguments,
    ])


def test_the_control_and_the_planted_faults_read_over_their_limits(tmp_path):
    """The reference on bfloat16 features, and the reference with a
    fault planted, put in the program's place. The control need not fail
    every number: a bfloat16 naive Bayes table and a bfloat16 forest's
    thresholds are as good as float32 ones (PERF.md)."""
    reading = readings("--seeds", "5,77,2147483777", "--control", "1", "--faults", "1",
                       "--keep", str(tmp_path))
    limits = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "higgs-11m.json")))["limits"]
    for name in ("lr_prob_gap", "nb_pred_gap", "tree_pred_gap", "dt_leaf_gap",
                 "dt_split_gap", "dt_loss_gap", "gb_leaf_gap", "gb_split_gap"):
        assert reading["control"][name] > limits[name], name
        assert reading["control"][name] >= 3 * reading["numbers"][name], name
    for fault, names in {
        "half_batch": ("dt_leaf_gap", "rf_leaf_z", "gb_leaf_gap", "nb_prior_gap"),
        "first_feature": ("dt_split_gap", "gb_split_gap", "ensemble_loss_gap"),
        "wrong_split": ("nb_theta_gap",),
        "random_threshold": ("rf_split_gap",),
    }.items():
        for name in names:
            assert reading["faults"][fault][name] > limits[name], (fault, name)
    # what a build left behind, kept, reads the same without the program
    again = readings("--seeds", "2147483777", "--from", str(tmp_path))
    assert again["numbers"] == reading["numbers"]


@pytest.mark.parametrize("fault,caught_by", [
    # the program's own path in the precision below the stated float32
    ("bf16", {"nb_pred_gap", "lr_prob_gap", "dt_split_gap", "gb_leaf_gap"}),
    ("half_batch", {"dt_leaf_gap", "rf_leaf_z", "gb_leaf_gap", "nb_prior_gap"}),
    ("state_unchanged", {"violations"}),
    ("later_builds_unchanged", {"violations"}),
    ("poor_splits", {"dt_split_gap", "gb_split_gap", "ensemble_loss_gap"}),
    ("answer_altered", {"tree_pred_gap", "nb_pred_gap", "lr_prob_gap"}),
])
def test_a_broken_timed_path_is_not_correct(fault, caught_by):
    result = drive(fault)
    assert result["correct"] is False
    assert caught_by <= over(result)


def test_a_build_that_fails_ends_the_window_and_the_run_is_not_correct():
    result = drive("second_build_fails")
    assert result["correct"] is False
    assert (result["attempted"], result["failed"]) == (2, 1)
    assert len(result["harness"]["build_s"]) == 1
