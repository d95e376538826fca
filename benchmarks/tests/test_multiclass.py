"""Labels of more than two classes. The plain reference's K-class
routines against computations of their own and, at two classes, against
the routines the binary cells use; the configuration's ``label``
contract; and a rehearsal of a K-class configuration that no cell lists
(``data/multiclass.json``, rows from ``multiclass_driver.make``), at 3
and at 10 classes: the reference's own outputs in the published layout,
and the program's through ``POST /models``, sound and with a fault
planted."""

import json
import os
import sys
import zipfile

import numpy as np
import pytest

import multiclass_driver as driver
from lib import cells, correct
from test_correct import last_line, over

HERE = os.path.dirname(os.path.abspath(__file__))
ref = cells.load_module("reference", "classifiers")


def softmax_rows(seed=3, n=20000, d=12, classes=4):
    """Raw columns of scales 0.1 to 50 and labels from a noisy linear
    score of them."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)) * rng.uniform(0.1, 50, size=d) + 3
    X = np.asfortranarray(X.astype(np.float32))
    W = rng.normal(size=(d, classes)) / np.abs(X).mean(axis=0)[:, None]
    y = (X @ W + rng.gumbel(size=(n, classes))).argmax(axis=1)
    return X, y


# --- the reference's K-class routines -------------------------------------

def test_naive_bayes_against_direct_class_counts():
    rng = np.random.default_rng(11)
    X = np.asfortranarray(rng.integers(0, 5, size=(3000, 6)).astype(np.float32))
    y = rng.integers(0, 4, size=3000)
    theta, prior = ref.nb_fit(X, y, 1.0, classes=4)
    assert theta.shape == (4, 6) and prior.shape == (4,)
    for c in range(4):
        counts = X[y == c].astype(np.float64).sum(axis=0) + 1.0
        assert np.abs(theta[c] - np.log(counts / counts.sum())).max() < 1e-12
        assert abs(prior[c] - np.log((y == c).mean())) < 1e-12
    joint = X.astype(np.float64) @ theta.T + prior
    want = np.exp(joint - joint.max(axis=1, keepdims=True))
    assert np.abs(ref.nb_proba((theta, prior), X) - want / want.sum(axis=1, keepdims=True)).max() < 1e-12


def test_the_multinomial_s_gradient_vanishes_at_its_optimum():
    X, y = softmax_rows()
    coef, intercept = ref.lr_fit(X, y, classes=4)
    assert coef.shape == (12, 3) and intercept.shape == (3,)
    W = np.vstack([coef, intercept])
    at_zero = np.linalg.norm(ref.softmax_state(X, y, np.zeros_like(W))["gradient"])
    assert np.linalg.norm(ref.softmax_state(X, y, W)["gradient"]) < 1e-9 * at_zero
    P = ref.lr_proba((coef, intercept), X)
    assert P.shape == (len(X), 4) and np.abs(P.sum(axis=1) - 1).max() < 1e-12


def test_a_hessian_vector_product_against_finite_differences():
    X, y = softmax_rows()
    rng = np.random.default_rng(5)
    W = rng.normal(size=(13, 3)) * 0.01
    V = rng.normal(size=W.shape)
    eps = 1e-6
    step = (
        ref.softmax_state(X, y, W + eps * V)["gradient"]
        - ref.softmax_state(X, y, W - eps * V)["gradient"]
    ) / (2 * eps)
    product = ref.softmax_hvp(X, ref.softmax_state(X, y, W)["P"], V)
    assert np.abs(step - product).max() < 1e-6 * np.abs(product).max()


def test_weighted_f1_by_hand_on_four_classes():
    truth = np.array([0, 0, 0, 1, 1, 2, 2, 2, 3, 3])
    labels = np.array([0, 0, 1, 1, 2, 2, 2, 0, 3, 1])
    accuracy, f1 = ref.accuracy_f1(labels, truth, classes=4)
    # F1 by class 2/3, 0.4, 2/3, 2/3, weighted 0.3, 0.2, 0.3, 0.2
    assert accuracy == 0.6 and f1 == pytest.approx(46 / 75, abs=1e-15)


@pytest.mark.parametrize("routine", ["nb_sums", "lr_proba", "logloss"])
def test_at_two_classes_the_k_class_routines_are_the_binary_ones(routine):
    X, y = softmax_rows(classes=2)
    if routine == "nb_sums":
        X = np.abs(X)
        today, k_class = ref._binary_sums(X, y)(0, len(X)), ref._class_sums(X, y, 2)(0, len(X))
        for a, b in zip(today, k_class):
            assert np.abs(a - b).max() <= 1e-12 * np.abs(a).max()
    elif routine == "lr_proba":
        today = ref.lr_proba(ref.lr_fit(X, y), X)
        assert np.abs(today - ref.lr_proba(ref.softmax_fit(X, y, 2), X)).max() < 1e-12
    else:
        p = np.random.default_rng(2).uniform(0.01, 0.99, size=len(y))
        # a third class that no row holds: the K-class expression on the
        # binary probabilities
        wide = np.stack([1 - p, p, np.zeros_like(p)], axis=1)
        assert abs(ref.logloss_k(wide, y) - ref.logloss(p, y)) < 1e-12
        assert ref.logloss_k(wide[:, :2], y) == ref.logloss(p, y)


# --- the configuration's label ----------------------------------------------

def test_the_committed_configurations_are_binary():
    spec = cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    for workload in spec["workloads"]:
        cell = cells.Cell(workload["name"])
        assert cell.config["label"] == "binary" and cell.classes == 2


@pytest.mark.parametrize("label", [
    "multiclass", {"classes": 2}, {"classes": 3.0}, {"classes": 3, "names": []}, None,
])
def test_a_label_the_harness_cannot_check_is_refused(label):
    config = driver.configuration(3)
    config["label"] = label
    with pytest.raises(ValueError, match="label"):
        correct.label_classes(config)


def test_a_configuration_without_a_label_is_binary():
    config = driver.configuration(3)
    del config["label"]
    config["classifiers"].append("gb")
    assert correct.label_classes(config) == 2


def test_a_k_class_configuration_that_lists_gb_is_refused_when_its_cell_loads(tmp_path):
    root = driver.grown_root(str(tmp_path), 3)
    assert cells.Cell(driver.WORKLOAD, root=root).classes == 3
    path = os.path.join(root, "multiclass-test.json")
    config = cells.read_json(path)
    config["classifiers"].append("gb")
    with open(path, "w") as handle:
        json.dump(config, handle)
    with pytest.raises(ValueError, match="gradient-boosted trees are binary only.*GBTClassifier"):
        cells.Cell(driver.WORKLOAD, root=root)


# --- a rehearsal of the reference's own outputs -----------------------------

class Published:
    """What ``read_build`` reads of a system, in memory: metadata rows,
    stored columns, and checkpoints written in the published layout."""

    def __init__(self, models_dir):
        self.models_dir = models_dir
        self.rows, self.columns = {}, {}
        self.store = self

    def find_one(self, name, projection):
        return self.rows.get(name)

    def stored(self, name, fields):
        return {field: self.columns[name][field] for field in fields}

    def publish(self, name, clf, kind, arrays, scalars, proba, y_test, classes):
        path = os.path.join(self.models_dir, f"{name}.model")
        with open(path, "wb") as handle:
            np.savez(handle, **arrays)
        with zipfile.ZipFile(path, "a") as archive:
            archive.writestr("__model__.json", json.dumps({"kind": kind, "scalars": scalars}))
        labels = proba.argmax(axis=1)
        accuracy, f1 = ref.accuracy_f1(labels, y_test, classes)
        self.rows[name] = {
            "classificator": clf, "model_checkpoint": path, "accuracy": accuracy, "F1": f1,
        }
        self.columns[name] = {
            "label": y_test.astype(np.float64), "prediction": labels.astype(np.float64),
            "probability": proba,
        }


NAMES = {"test": "multiclass_test"}


def reference_build(comparison, models_dir):
    """The reference in the program's place: its fits, published."""
    c, hyper = comparison, comparison.hyper
    bins, depth = int(hyper["max_bins"]), int(hyper["max_depth"])
    system = Published(models_dir)
    coef, intercept = c.fits["lr"]
    theta, prior = c.fits["nb"]
    pad = np.zeros((coef.shape[0], 1))
    published = {
        "lr": ("logistic", {"w": np.hstack([pad, coef]), "b": np.r_[0.0, intercept]}, {}),
        "nb": ("naive_bayes", {"theta": theta, "prior": prior}, {}),
        "dt": ref.grow_model(c.bins, c.thresholds, c.y_train, bins, depth, classes=c.classes),
        "rf": ref.grow_forest(
            c.bins, c.thresholds, c.y_train, bins, depth, int(hyper["rf_trees"]),
            c.seed + 1, classes=c.classes,
        ),
    }
    for clf, model in published.items():
        if clf in ("lr", "nb"):
            kind, arrays, scalars = model
            proba = (
                ref.lr_proba(c.fits["lr"], c.X_test) if clf == "lr"
                else ref.nb_proba(c.fits["nb"], c.X_test)
            )
        else:
            kind, scalars = model["kind"], {"max_depth": depth}
            arrays = {k: model[k] for k in ("features_heap", "thresholds_heap", "leaf_probs")}
            proba = correct.model_proba(ref, model, c.X_test)
        system.publish(
            f"{NAMES['test']}_prediction_{clf}", clf, kind, arrays, scalars, proba,
            c.y_test, c.classes,
        )
    return system


@pytest.fixture(scope="module", params=[3, 10])
def rehearsal(request, tmp_path_factory):
    classes = request.param
    config = driver.configuration(classes)
    rows = config["rows"]
    columns, labels, _ = driver.make(config["dataset"], driver.SEED, rows["train"] + rows["test"])
    X, n = ref.as_matrix(columns, dtype=np.float32), rows["train"]
    assert set(np.unique(labels)) == set(range(classes))

    def comparison():
        return correct.Comparison(ref, config, driver.SEED, X[:n], labels[:n], X[n:], labels[n:])

    c = comparison()
    system = reference_build(c, str(tmp_path_factory.mktemp(f"models{classes}")))
    return config, comparison, c, system


def read(system, config, c):
    return correct.read_build(
        system, ref, config, NAMES, system.models_dir, 0.0, c.y_test, c.sample
    )


def test_the_reference_s_outputs_in_the_published_layout_are_correct(rehearsal):
    config, _, c, system = rehearsal
    outputs, violations = read(system, config, c)
    assert violations == []
    assert all(out["proba"].shape == (len(c.sample), c.classes) for out in outputs.values())
    c.compare(outputs)
    ok, compared = c.verdict()
    assert ok, {k: v for k, v in compared.items() if v["value"] > v["limit"]}
    assert set(compared) == set(config["limits"]) | {"violations"}


@pytest.mark.parametrize("fault, caught_by", [
    ("binary", {"lr_prob_gap", "nb_prior_gap", "dt_leaf_gap", "rf_leaf_z", "dt_loss_gap"}),
    ("swapped", {"lr_prob_gap", "nb_pred_gap", "tree_pred_gap"}),
    ("first_feature", {"dt_split_gap"}),
])
def test_each_fault_fails_by_a_named_number(rehearsal, fault, caught_by):
    """The reference with a fault planted, put in the program's place."""
    config, comparison, _, system = rehearsal
    c = comparison()
    outputs, _ = read(system, config, c)
    readings = correct.fault_numbers(c, outputs)[fault]
    for name in caught_by:
        assert readings[name] > config["limits"][name], (name, readings[name])


def test_read_build_holds_each_row_to_its_classes(rehearsal):
    """A stored label that is not its row's top class, a row that does
    not sum to 1, and a row two wide are each a violation."""
    config, _, c, system = rehearsal
    name = f"{NAMES['test']}_prediction_nb"
    kept = dict(system.columns[name])
    try:
        proba = kept["probability"].copy()
        proba[:, [1, 2]] = proba[:, [2, 1]]
        for stored, says in [
            (proba, "not its probability's"),
            (kept["probability"] * 1.001, "does not sum to 1"),
            (kept["probability"][:, :2], "of shape"),
        ]:
            system.columns[name] = dict(kept, probability=stored)
            _, violations = read(system, config, c)
            assert len(violations) == 1 and says in violations[0], violations
    finally:
        system.columns[name] = kept


# --- the same through the program ---------------------------------------------

ROWS = 24000


@pytest.mark.parametrize("classes", [3, 10])
@pytest.mark.parametrize("fault, caught_by", [
    ("none", set()),
    # every fit on labels folded to two classes: rows two wide
    ("binary", {"violations"}),
    ("swapped", {"violations", "lr_prob_gap", "nb_pred_gap", "tree_pred_gap"}),
    # every node of every tree split on its first feature's median
    ("poor_splits", {"dt_split_gap"}),
])
def test_a_rehearsal_through_the_program_is_sound_and_a_broken_one_is_not(
    tmp_path, classes, fault, caught_by
):
    result = last_line([
        sys.executable, os.path.join(HERE, "multiclass_driver.py"), fault, str(classes),
        str(ROWS), str(tmp_path),
    ])
    assert result["rehearsal"] is True and result["failed"] == 0
    assert set(result["compared"]) - {"violations"} == set(
        correct.expected_numbers(["lr", "dt", "rf", "nb"])
    )
    if fault == "none":
        assert result["correct"] is True and over(result) == set()
    else:
        assert result["correct"] is False and caught_by <= over(result)
