"""The plain reference's tree arithmetic against hand counts, and its
audit against trees it grew itself and trees that were spoiled."""

import numpy as np
import pytest

from lib import cells

ref = cells.load_module("reference", "classifiers")


def test_thresholds_and_bins_by_hand():
    X = np.asfortranarray(np.arange(8, dtype=np.float32).reshape(8, 1))
    cuts = ref.quantile_thresholds(X, 4)
    assert cuts.tolist() == [[1.75, 3.5, 5.25]]
    # bin b holds cuts[b-1] < x <= cuts[b]
    assert ref.bin_matrix(X, cuts)[:, 0].tolist() == [0, 0, 1, 1, 2, 2, 3, 3]


def test_gini_gain_by_hand():
    # one node, one feature, two bins: left 3 of class 0 and 1 of class 1,
    # right 1 and 3: 2 * (9 + 1) / 4 - (16 + 16) / 8 = 1
    hist = np.array([[[[3.0, 1.0]]], [[[1.0, 3.0]]]])
    gains = ref.gini_gains(hist)
    assert gains[0, 0, 0] == pytest.approx(1.0)
    assert gains[0, 0, 1] == -np.inf  # nothing to the right of the last bin


def test_newton_gain_by_hand():
    hist = np.array([[[[2.0, -2.0]]], [[[1.0, 1.0]]]])  # g and h by bin
    gains = ref.newton_gains(hist, lam=1.0)
    assert gains[0, 0, 0] == pytest.approx(4 / 2 + 4 / 2 - 0.0)


@pytest.fixture(scope="module")
def grown():
    rng = np.random.default_rng(5)
    X = np.asfortranarray(rng.normal(size=(40000, 5)).astype(np.float32))
    y = ((X[:, 0] * X[:, 1] + 0.4 * X[:, 2] + 0.5 * rng.normal(size=len(X))) > 0).astype(np.int64)
    cuts = ref.quantile_thresholds(X, 32)
    bins = ref.bin_matrix(X, cuts)
    return X, y, cuts, bins, ref.grow_model(bins, cuts, y, 32, 4)


def audit(grown, features):
    X, y, cuts, bins, tree = grown
    leaf = ref.route_blocks(X, features, tree["thresholds_heap"][0], 4)
    return ref.audit_tree(bins, leaf, features, ref.class_channels(y), "gini", 4, 32)


def test_the_audit_finds_nothing_on_the_reference_s_own_tree(grown):
    *_, tree = grown
    best, own, chosen, weight, sums = audit(grown, tree["features_heap"][0])
    assert np.allclose(best, chosen) and np.allclose(own, chosen)
    assert weight[0] == 40000 and weight[1] + weight[2] == 40000
    shares, count = ref.leaf_shares(sums)
    assert np.allclose(shares, tree["leaf_probs"][0]) and count.sum() == 40000


def test_the_audit_sees_a_spoiled_split(grown):
    *_, tree = grown
    features = tree["features_heap"][0].copy()
    features[0] = 4  # the root now splits on a column the label ignores
    best, _, chosen, *_ = audit(grown, features)
    assert chosen[0] < 0.05 * best[0]


def test_a_forest_and_a_booster_in_the_published_layout(grown):
    X, y, cuts, bins, _ = grown
    forest = ref.grow_forest(bins, cuts, y, 32, 3, trees=2, seed=1)
    assert forest["features_heap"].shape == (2, 7) and forest["leaf_probs"].shape == (2, 8, 2)
    boosted = ref.grow_boosted(bins, cuts, y, 32, 3, 2, 0.1, 1.0, 1e-6)
    p = ref.gbt_proba(X, boosted["f0"], boosted["step"], boosted["features_heap"],
                      boosted["thresholds_heap"], boosted["leaf_values"], 3)[:, 1]
    base = np.full(len(y), y.mean())
    assert ref.logloss(p, y) < ref.logloss(base, y)
