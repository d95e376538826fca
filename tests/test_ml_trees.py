"""Tree estimators vs sklearn oracles and invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import sklearn.ensemble
import sklearn.tree

from learningorchestra_tpu.ml import trees
from learningorchestra_tpu.ml.base import make_classifier, prepare_xy
from learningorchestra_tpu.ml.binning import apply_bins, make_thresholds
from learningorchestra_tpu.ml.evaluation import accuracy_score
from learningorchestra_tpu.ml.trees import (
    DecisionTreeClassifier,
    GBTClassifier,
    RandomForestClassifier,
)


@pytest.fixture()
def nonlinear(rng):
    """XOR-ish data no linear model can fit: tests real tree splits."""
    n = 800
    X = rng.normal(size=(n, 6))
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(int)
    return X, y


@pytest.fixture()
def three_class(rng):
    n = 900
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] > 0.5).astype(int) + (X[:, 1] > 0).astype(int)
    return X, y


def _searchsorted_bins(X, thresholds):
    """The reference ``apply_bins`` is held to, one column at a time."""
    return np.stack(
        [
            np.searchsorted(thresholds[f], X[:, f], side="left")
            for f in range(X.shape[1])
        ],
        axis=1,
    )


def _on_thresholds(rng):
    X = rng.normal(size=(512, 4))
    # a float32 copy of every threshold back among the values, for both
    # bin counts the test runs
    for max_bins in (32, 200):
        ties = make_thresholds(X, max_bins).astype(np.float32).T
        X = np.concatenate([X, ties])
    return X


def _odd_values(rng):
    X = rng.normal(size=(640, 5))
    for value in (np.nan, np.inf, -np.inf, -0.0, 0.0):
        X[rng.integers(0, 640, 40), rng.integers(0, 5, 40)] = value
    X[:, 4] = np.where(rng.random(640) < 0.5, -0.0, X[:, 4])
    return X


def _with_column(rng, column):
    X = rng.normal(size=(400, 3))
    X[:, 1] = column
    return X


_BINNING_CASES = {
    "random_normal": lambda rng: rng.normal(size=(500, 6)),
    "on_thresholds": _on_thresholds,
    "three_levels": lambda rng: _with_column(rng, rng.integers(0, 3, 400)),
    "constant_column": lambda rng: _with_column(rng, 2.5),
    "all_nan_column": lambda rng: _with_column(rng, np.nan),
    "nan_inf_negative_zero": _odd_values,
    "rows_257": lambda rng: rng.normal(size=(257, 28)),
}


class TestBinning:
    def test_bins_are_monotone_with_values(self, rng):
        X = rng.normal(size=(500, 3))
        thresholds = make_thresholds(X, 32)
        bins = np.asarray(apply_bins(X.astype(np.float32), thresholds.astype(np.float32)))
        for f in range(3):
            order = np.argsort(X[:, f])
            assert (np.diff(bins[order, f]) >= 0).all()
        assert bins.min() >= 0 and bins.max() < 32

    def test_threshold_semantics(self):
        # bin b holds thresholds[b-1] < x <= thresholds[b]
        X = np.array([[1.0], [2.0], [3.0], [4.0]])
        thresholds = np.array([[1.5, 2.5, 3.5]])
        bins = np.asarray(apply_bins(X.astype(np.float32), thresholds.astype(np.float32)))
        assert bins[:, 0].tolist() == [0, 1, 2, 3]

    @pytest.mark.parametrize("max_bins", [32, 200])
    @pytest.mark.parametrize("case", sorted(_BINNING_CASES))
    def test_equals_searchsorted_left(self, rng, case, max_bins):
        """Every bin is the integer ``np.searchsorted(side="left")``
        gives per column: ties go left, repeats and ``+inf`` thresholds
        are no special case, NaN lands in the last bin."""
        X = _BINNING_CASES[case](rng).astype(np.float32)
        # quantiles of the finite values: `make_thresholds` interpolates
        # between two equal infinities to NaN -> +inf, out of order, and
        # a search is defined on sorted thresholds only
        finite = np.where(np.isinf(X), np.nan, X)
        thresholds = make_thresholds(finite, max_bins).astype(np.float32)
        assert (thresholds[:, 1:] >= thresholds[:, :-1]).all()
        bins = np.asarray(apply_bins(X, thresholds))
        assert bins.dtype == (np.int8 if max_bins <= 127 else np.int32)
        np.testing.assert_array_equal(bins, _searchsorted_bins(X, thresholds))


class TestDecisionTree:
    def test_solves_xor(self, nonlinear):
        X, y = nonlinear
        model = DecisionTreeClassifier().fit(X, y)
        assert accuracy_score(y, model.predict(X)) > 0.9

    def test_close_to_sklearn(self, three_class):
        X, y = three_class
        ours = DecisionTreeClassifier(max_depth=5).fit(X, y).predict(X)
        theirs = (
            sklearn.tree.DecisionTreeClassifier(max_depth=5, random_state=0)
            .fit(X, y)
            .predict(X)
        )
        assert np.mean(ours == theirs) > 0.9

    def test_proba_normalized(self, nonlinear):
        X, y = nonlinear
        probs = DecisionTreeClassifier().fit(X, y).predict_proba(X)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-4)

    def test_pure_node_stops_splitting(self, rng):
        # Perfectly separable on one feature: tree must be exact.
        X = rng.normal(size=(200, 3))
        y = (X[:, 2] > 0).astype(int)
        model = DecisionTreeClassifier().fit(X, y)
        assert accuracy_score(y, model.predict(X)) == 1.0


class TestRandomForest:
    def test_solves_xor(self, nonlinear):
        X, y = nonlinear
        model = RandomForestClassifier().fit(X, y)
        assert accuracy_score(y, model.predict(X)) > 0.9

    def test_multiclass(self, three_class):
        X, y = three_class
        model = RandomForestClassifier().fit(X, y)
        assert accuracy_score(y, model.predict(X)) > 0.85

    def test_comparable_to_sklearn_generalization(self, rng):
        n = 1200
        X = rng.normal(size=(n, 6))
        y = ((X[:, 0] * X[:, 1] > 0) & (X[:, 2] > -0.5)).astype(int)
        X_train, X_test = X[:800], X[800:]
        y_train, y_test = y[:800], y[800:]
        ours = RandomForestClassifier().fit(X_train, y_train)
        theirs = sklearn.ensemble.RandomForestClassifier(
            n_estimators=20, max_depth=5, random_state=0
        ).fit(X_train, y_train)
        ours_acc = accuracy_score(y_test, ours.predict(X_test))
        theirs_acc = theirs.score(X_test, y_test)
        assert ours_acc > theirs_acc - 0.07


class TestGBT:
    def test_solves_xor(self, nonlinear):
        X, y = nonlinear
        model = GBTClassifier().fit(X, y)
        assert accuracy_score(y, model.predict(X)) > 0.9

    def test_rejects_multiclass(self, three_class):
        X, y = three_class
        with pytest.raises(ValueError):
            GBTClassifier().fit(X, y)

    def test_proba_binary_shape(self, nonlinear):
        X, y = nonlinear
        probs = GBTClassifier(rounds=5).fit(X, y).predict_proba(X)
        assert probs.shape == (len(X), 2)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)

    def test_comparable_to_sklearn_generalization(self, rng):
        n = 1200
        X = rng.normal(size=(n, 6))
        y = ((X[:, 0] * X[:, 1] > 0) & (X[:, 2] > -0.5)).astype(int)
        X_train, X_test = X[:800], X[800:]
        y_train, y_test = y[:800], y[800:]
        ours = GBTClassifier().fit(X_train, y_train)
        theirs = sklearn.ensemble.GradientBoostingClassifier(
            n_estimators=20, max_depth=5, random_state=0
        ).fit(X_train, y_train)
        ours_acc = accuracy_score(y_test, ours.predict(X_test))
        theirs_acc = theirs.score(X_test, y_test)
        assert ours_acc > theirs_acc - 0.07


class TestSwitcher:
    def test_all_five_names(self, nonlinear):
        X, y = nonlinear
        for name in ("lr", "dt", "rf", "gb", "nb"):
            clf = make_classifier(name)
            model = clf.fit(np.abs(X) if name == "nb" else X, y)
            assert model.predict(X[:10]).shape == (10,)

    def test_unknown_name_raises(self):
        with pytest.raises(KeyError):
            make_classifier("svm")


class TestNaNRouting:
    def test_nan_rows_route_same_at_fit_and_predict(self, rng):
        # NaN in the split feature: training bins NaN into the last bin
        # (right); prediction must send it right too.
        n = 400
        X = rng.normal(size=(n, 2))
        X[: n // 4, 0] = np.nan
        y = np.where(np.isnan(X[:, 0]), 1, (X[:, 0] > 0).astype(int))
        model = DecisionTreeClassifier().fit(X, y)
        pred = model.predict(X)
        nan_rows = np.isnan(X[:, 0])
        assert (pred[nan_rows] == 1).mean() > 0.95
        assert accuracy_score(y, pred) > 0.95


def test_deep_tree_wide_level_routing():
    # depth > 6 exercises the _indicator_lookup gather fallback (a
    # (rows, 2^depth) indicator would dwarf the gather it replaces)
    import numpy as np

    from learningorchestra_tpu.ml.trees import DecisionTreeClassifier

    rng = np.random.default_rng(2)
    X = rng.normal(size=(2000, 6))
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0) ^ (X[:, 2] > 0.5)).astype(np.int32)
    model = DecisionTreeClassifier(max_depth=8).fit(X, y)
    accuracy, _ = model.evaluate(X, y)
    assert accuracy > 0.95


class TestNamedScopes:
    """The stages of a tree fit name their XLA ops (``lo.hist``,
    ``lo.split``, ``lo.route``, ``lo.leaf``; ``lo.bin`` in binning), so
    a profiler capture says which stage an op belongs to — and the
    names change nothing of the fit."""

    def _inputs(self, rng):
        X = rng.normal(size=(256, 4)).astype(np.float32)
        y = ((X[:, 0] > 0) ^ (X[:, 2] > 0.3)).astype(np.int32)
        thresholds = make_thresholds(X, 32).astype(np.float32)
        bins = apply_bins(X, thresholds)
        return bins, jnp.asarray(y), jnp.ones(256, jnp.float32)

    @pytest.mark.parametrize(
        "scope", ["lo.hist", "lo.split", "lo.route", "lo.leaf"]
    )
    def test_stage_names_reach_the_lowered_program(self, rng, scope):
        bins, y, weights = self._inputs(rng)
        text = trees._dt_fit.lower(bins, y, weights, 2, 3, 32).as_text(
            debug_info=True
        )
        assert scope in text

    def test_binning_names_its_search(self, rng):
        X = rng.normal(size=(64, 3)).astype(np.float32)
        thresholds = make_thresholds(X, 32).astype(np.float32)
        text = apply_bins.lower(X, thresholds).as_text(debug_info=True)
        assert "lo.bin" in text

    def test_binning_lowers_to_no_gather_and_no_loop(self, rng):
        # a binary search lowers to a `while` of per-value gathers, which
        # the TPU serialises: 35 of a build's 77 s (PERF.md §6, PR 27)
        X = rng.normal(size=(64, 3)).astype(np.float32)
        thresholds = make_thresholds(X, 32).astype(np.float32)
        text = apply_bins.lower(X, thresholds).as_text()
        assert "compare" in text
        assert "gather" not in text
        assert "while" not in text

    def test_fitted_tree_is_bit_identical_without_the_names(
        self, rng, monkeypatch
    ):
        import contextlib

        bins, y, weights = self._inputs(rng)
        named = trees._dt_fit(bins, y, weights, 2, 4, 32)
        monkeypatch.setattr(
            jax, "named_scope", lambda name: contextlib.nullcontext()
        )
        # a function of its own: the same one would be served from
        # jax's trace cache, names and all
        unnamed_fit = jax.jit(
            lambda *arrays: trees._dt_fit.__wrapped__(*arrays, 2, 4, 32)
        )
        assert "lo.hist" not in unnamed_fit.lower(bins, y, weights).as_text(
            debug_info=True
        )
        unnamed = unnamed_fit(bins, y, weights)
        for with_names, without in zip(named, unnamed):
            assert np.asarray(with_names).tobytes() == (
                np.asarray(without).tobytes()
            )


class TestFitsOnReferenceBins:
    """The three tree classifiers publish, bit for bit, what the same
    fit programs give on bins computed by ``np.searchsorted``."""

    def _data(self, rng):
        X = rng.normal(size=(700, 6))
        X[rng.integers(0, 700, 30), rng.integers(0, 6, 30)] = np.nan
        X[:, 5] = rng.integers(0, 3, 700)
        y = ((X[:, 0] > 0) ^ (X[:, 5] > 0.5)).astype(int)
        return X, y

    def _reference_inputs(self, X, y, mesh):
        thresholds = make_thresholds(X, 32).astype(np.float32)
        X_dev, y_dev, mask = prepare_xy(X, y, mesh)
        bins = _searchsorted_bins(np.asarray(X_dev), thresholds)
        return (
            jnp.asarray(bins, jnp.int8),
            y_dev,
            mask.astype(jnp.float32),
            jnp.asarray(thresholds),
        )

    @staticmethod
    def _same(published, replayed):
        for ours, theirs in zip(published, replayed):
            ours, theirs = np.asarray(ours), np.asarray(theirs)
            assert ours.dtype == theirs.dtype and ours.shape == theirs.shape
            assert ours.tobytes() == theirs.tobytes()

    def test_decision_tree(self, rng):
        X, y = self._data(rng)
        model = DecisionTreeClassifier(max_depth=4).fit(X, y)
        bins, y_dev, weights, thresholds = self._reference_inputs(X, y, model.mesh)
        features, bin_heap, leaves = trees._dt_fit(bins, y_dev, weights, 2, 4, 32)
        self._same(
            (model.features_heap, model.thresholds_heap, model.leaf_probs),
            (
                features[None],
                trees._heap_thresholds(features, bin_heap, thresholds)[None],
                leaves[None],
            ),
        )

    def test_random_forest(self, rng):
        X, y = self._data(rng)
        model = RandomForestClassifier(num_trees=5, max_depth=4, seed=7).fit(X, y)
        bins, y_dev, weights, thresholds = self._reference_inputs(X, y, model.mesh)
        features, bin_heap, leaves = trees._rf_fit(
            bins, y_dev, weights, jax.random.key(7), 2, 4, 32, 5, 3,
            mesh=model.mesh,
        )
        self._same(
            (model.features_heap, model.thresholds_heap, model.leaf_probs),
            (
                features,
                trees._heap_thresholds(features, bin_heap, thresholds),
                leaves,
            ),
        )

    def test_gbt(self, rng):
        X, y = self._data(rng)
        model = GBTClassifier(rounds=4, step=0.1, max_depth=3).fit(X, y)
        bins, y_dev, weights, thresholds = self._reference_inputs(X, y, model.mesh)
        f0, features, bin_heap, leaves = trees._gbt_fit(
            bins, y_dev, weights, 3, 32, 4, jnp.float32(0.1)
        )
        self._same(
            (model.f0, model.features_heap, model.thresholds_heap, model.leaf_values),
            (
                f0,
                features,
                trees._heap_thresholds(features, bin_heap, thresholds),
                leaves,
            ),
        )
