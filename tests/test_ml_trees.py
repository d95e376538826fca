"""Tree estimators vs sklearn oracles and invariants."""

import json
import os
import re
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import sklearn.ensemble
import sklearn.tree

from learningorchestra_tpu.ml import trees
from learningorchestra_tpu.ml.base import make_classifier, prepare_xy
from learningorchestra_tpu.ml import binning
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


def _numpy_thresholds(X, max_bins):
    """What the device pass is held to: numpy's own quantiles of the
    finite float32 values, in float64, rounded once to float32; a
    column with none all ``+inf``."""
    values = np.asarray(X, np.float32).astype(np.float64)
    values[np.isinf(values)] = np.nan
    quantiles = np.linspace(0, 1, max_bins + 1)[1:-1]
    with np.errstate(all="ignore"), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        thresholds = np.nanquantile(values, quantiles, axis=0).T
    return np.nan_to_num(thresholds, nan=np.inf).astype(np.float32)


def _some_nans(rng):
    X = rng.normal(size=(257, 5))
    X[rng.integers(0, 257, 60), rng.integers(0, 5, 60)] = np.nan
    return X


def _zeros_of_both_signs(rng):
    X = rng.normal(size=(257, 5))
    X[:, 0] = np.where(rng.random(257) < 0.5, -0.0, 0.0)
    X[:, 1] = np.where(rng.random(257) < 0.7, -0.0, X[:, 1])
    X[:, 2] = np.where(rng.random(257) < 0.7, 0.0, -np.abs(X[:, 2]))
    return X


def _denormals(rng):
    X = rng.normal(size=(257, 5))
    X[:, :3] *= 1e-42  # float32 holds these as denormals only
    X[:, 3] = np.where(rng.random(257) < 0.5, 1e-45, -1e-45)
    return X


def _indicator_columns(rng=None):
    """What a one-hot coded table holds: 0/1 columns whose level has
    1/1000, exactly 1/32, 1/2 and 31/32 of 32,000 rows, a constant
    column and a three-valued one, the rows in an order of their own a
    column. At 1/32 and 31/32 one quantile's rank falls between the
    last 0 and the first 1 (0.03125 and 0.96875), at 1/2 the median
    does (0.5): a third threshold, and still two bins."""
    rng = np.random.default_rng(700)
    rows = 32000
    columns = [
        rng.permutation((np.arange(rows) < ones).astype(np.float64))
        for ones in (rows // 1000, rows // 32, rows // 2, rows * 31 // 32)
    ]
    columns.append(np.ones(rows))
    columns.append(rng.permutation(np.arange(rows) % 3).astype(np.float64))
    return np.stack(columns, axis=1)


_THRESHOLD_CASES = {
    "indicator_columns": _indicator_columns,
    "heavy_ties": lambda rng: rng.integers(0, 4, size=(257, 5)) * 0.7,
    "constant_column": lambda rng: _with_column(rng, 2.5)[:257],
    "some_nans": _some_nans,
    "all_nan_column": lambda rng: _with_column(rng, np.nan)[:257],
    "one_row": lambda rng: rng.normal(size=(1, 5)),
    "two_rows": lambda rng: rng.normal(size=(2, 5)),
    "zeros_of_both_signs": _zeros_of_both_signs,
    "denormals": _denormals,
    "float64_values": lambda rng: rng.normal(size=(257, 5)) * 1e3 + 1e-9,
    "block_of_400_columns": lambda rng: rng.normal(size=(96, 400)),
}


class TestThresholdPass:
    """``make_thresholds`` / ``device_thresholds``: exact order
    statistics selected on the device, interpolated as numpy does."""

    @pytest.mark.parametrize("max_bins", [2, 32, 200])
    @pytest.mark.parametrize("case", sorted(_THRESHOLD_CASES))
    def test_equals_numpys_quantiles_of_the_float32_values(
        self, rng, case, max_bins
    ):
        X = _THRESHOLD_CASES[case](rng)
        got = make_thresholds(X, max_bins)
        assert got.dtype == np.float32
        assert got.shape == (X.shape[1], max_bins - 1)
        want = _numpy_thresholds(X, max_bins)
        # to the bit but for the sign of a zero: where a column holds
        # both, numpy's answer follows its partition's order
        assert np.array_equal(got, want)
        nonzero = want != 0
        assert np.array_equal(
            got.view(np.uint32)[nonzero], want.view(np.uint32)[nonzero]
        )
        assert not np.signbit(got[~nonzero]).any()

    @pytest.mark.parametrize("max_bins", [32, 200])
    def test_infinities_are_left_out_and_thresholds_stay_sorted(
        self, rng, max_bins
    ):
        X = rng.normal(size=(640, 4))
        X[rng.integers(0, 640, 8), 0] = -np.inf
        X[rng.integers(0, 640, 8), 1] = np.inf
        X[rng.integers(0, 640, 8), 2] = np.inf
        X[rng.integers(0, 640, 8), 2] = -np.inf
        X[:, 3] = np.where(rng.random(640) < 0.5, np.inf, -np.inf)
        got = make_thresholds(X, max_bins)
        assert (got[:, 1:] >= got[:, :-1]).all()
        assert np.isfinite(got[:3]).all() and np.isposinf(got[3]).all()
        assert np.array_equal(got, _numpy_thresholds(X, max_bins))

    def test_padding_rows_do_not_count(self, rng):
        X = rng.normal(size=(300, 6)).astype(np.float32)
        X[rng.integers(0, 300, 20), rng.integers(0, 6, 20)] = np.nan
        padded = np.concatenate(
            [X, rng.normal(size=(84, 6)).astype(np.float32) * 1e6]
        )
        padded[-3:] = [np.nan, np.inf, -np.inf, 0.0, -1e30, 1e30]
        mask = np.arange(384) < 300
        got = binning.device_thresholds(
            jnp.asarray(padded), jnp.asarray(mask), 32
        )
        assert np.array_equal(got, _numpy_thresholds(X, 32))

    @pytest.mark.parametrize("rows", [1000, 1021])
    def test_same_from_a_host_array_and_a_matrix_sharded_on_the_mesh(
        self, rng, rows
    ):
        X = rng.normal(size=(rows, 7))
        X[rng.integers(0, rows, 50), rng.integers(0, 7, 50)] = np.nan
        mesh = DecisionTreeClassifier().mesh
        assert mesh.devices.size == 8
        X_dev, _, mask = prepare_xy(X, None, mesh)
        assert len(X_dev.sharding.device_set) == 8
        on_mesh = binning.device_thresholds(X_dev, mask, 32)
        assert np.array_equal(on_mesh, make_thresholds(X, 32))
        assert np.array_equal(on_mesh, _numpy_thresholds(X, 32))

    @pytest.mark.parametrize(
        "column, share, distinct, values",
        [
            (0, "1/1000", 1, 2), (1, "1/32", 2, 2), (2, "1/2", 3, 2),
            (3, "31/32", 2, 2), (4, "constant", 1, 1), (5, "three-valued", 3, 3),
        ],
    )
    def test_indicator_columns_take_few_thresholds_and_few_bins(
        self, column, share, distinct, values
    ):
        X = _indicator_columns()
        thresholds = make_thresholds(X, 32)
        assert np.array_equal(thresholds, _numpy_thresholds(X, 32))
        mine = thresholds[column : column + 1]
        assert binning.distinct_thresholds(mine) == len(np.unique(mine)) == distinct
        # the bins against a count in numpy: thresholds under the value
        bins = np.asarray(apply_bins(jnp.asarray(X, jnp.float32), jnp.asarray(thresholds)))
        want = (mine < X[:, column : column + 1].astype(np.float32)).sum(axis=1)
        assert np.array_equal(bins[:, column], want)
        assert len(np.unique(bins[:, column])) == values
        # the whole table's count is the columns' summed
        assert binning.distinct_thresholds(thresholds) == sum(
            len(np.unique(row)) for row in thresholds
        )

    def test_distinct_thresholds_leaves_out_what_is_not_finite(self):
        table = np.array(
            [[0.0, 0.0, 1.0], [np.inf, np.inf, np.inf], [1.0, 2.0, 3.0], [5.0, 5.0, 5.0]],
            np.float32,
        )
        assert binning.distinct_thresholds(table) == 2 + 0 + 3 + 1

    def test_the_pass_lowers_to_no_sort_and_no_gather(self, rng):
        X = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)
        ranks = jnp.zeros((3, 62), jnp.int32)
        text = binning._bin_order_statistics.lower(
            X, jnp.ones(64, bool), ranks
        )
        assert "lo.quantile" in text.as_text(debug_info=True)
        assert "sort" not in text.as_text()
        assert "gather" not in text.as_text()


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


def _scatter_histogram(bins, node, channels, n_nodes, max_bins):
    """``(node, feature, bin, channel)`` sums by a plain numpy
    scatter-add, in float64."""
    rows, features = bins.shape
    hist = np.zeros((n_nodes, features, max_bins, channels.shape[1]))
    for f in range(features):
        np.add.at(hist, (node, f, bins[:, f]), channels)
    return hist


def _set_hist_geometry(patch, tile_rows, block, max_bins=32):
    """Shrink the tile loop of ``_level_histograms`` to ``tile_rows`` a
    step and ``block`` columns a feature block (the cap on the one
    bfloat16 indicator alive at a time, in bytes)."""
    patch.setattr(trees, "_HIST_TILE_ROWS", tile_rows)
    patch.setattr(trees, "_HIST_INDICATOR_BYTES", tile_rows * block * max_bins * 2)


class TestBlockedHistograms:
    """``_level_histograms`` with more than one row tile AND more than
    one feature block of several columns a level - the path every table
    of the benchmark takes (PERF.md §3: 8,388,608 x 28 walks 512 tiles
    of one block, 163,840 x 2,000 ten tiles of sixteen blocks): the
    same sums as a plain scatter-add and as the one-tile, one-block
    contraction."""

    ROWS, FEATURES, BLOCK, BINS, TILE = 2048, 400, 80, 32, 512

    def _inputs(self, rng, kind, n_nodes, rows=None):
        rows = rows or self.ROWS
        bins = rng.integers(0, self.BINS, (rows, self.FEATURES)).astype(np.int8)
        node = rng.integers(0, n_nodes, rows).astype(np.int32)
        if kind == "counts":  # class one-hots times a bootstrap weight
            label = rng.integers(0, 2, rows)
            weight = rng.poisson(1.0, rows)
            channels = np.eye(2)[label] * weight[:, None]
        else:  # Newton (g, h) pairs
            p = rng.random(rows)
            channels = np.stack([p - rng.integers(0, 2, rows), p * (1 - p)], 1)
        return bins, node, channels.astype(np.float32)

    def _histograms(self, bins, node, channels, n_nodes):
        return np.asarray(trees._level_histograms(
            jnp.asarray(bins), jnp.asarray(node), jnp.asarray(channels),
            n_nodes, self.BINS,
        ))

    @pytest.mark.parametrize("n_nodes", [1, 8])
    @pytest.mark.parametrize("kind", ["counts", "gradients"])
    def test_equals_scatter_add_and_the_one_block_result(
        self, rng, monkeypatch, kind, n_nodes
    ):
        bins, node, channels = self._inputs(rng, kind, n_nodes)
        want = _scatter_histogram(bins, node, channels, n_nodes, self.BINS)

        _set_hist_geometry(monkeypatch, self.TILE, self.BLOCK)
        plan = trees.hist_block_plan(self.ROWS, self.FEATURES, self.BINS)
        assert (plan["hist_block_features"], plan["hist_blocks"]) == (self.BLOCK, 5)
        assert plan["hist_tile_rows"] == self.TILE  # four tiles
        blocked = self._histograms(bins, node, channels, n_nodes)
        _set_hist_geometry(monkeypatch, self.ROWS, self.FEATURES)
        assert trees.hist_block_plan(self.ROWS, self.FEATURES, self.BINS)[
            "hist_blocks"
        ] == 1
        whole = self._histograms(bins, node, channels, n_nodes)
        assert blocked.shape == want.shape == whole.shape
        if kind == "counts":  # small integers: float32 sums are exact
            assert np.array_equal(blocked, want)
            assert np.array_equal(blocked, whole)
        else:
            np.testing.assert_allclose(blocked, want, atol=1e-5, rtol=0)
            np.testing.assert_allclose(blocked, whole, atol=1e-5, rtol=0)

    @pytest.mark.parametrize("kind", ["counts", "gradients"])
    def test_rows_the_tiles_do_not_divide_are_padded_with_weightless_rows(
        self, rng, monkeypatch, kind
    ):
        """2,000 rows in steps of 512: four tiles of 504 hold 2,016, and
        the sixteen rows of padding add nothing to any bin."""
        bins, node, channels = self._inputs(rng, kind, 4, rows=2000)
        _set_hist_geometry(monkeypatch, self.TILE, self.BLOCK)
        assert trees.hist_block_plan(2000, self.FEATURES, self.BINS)[
            "hist_tile_rows"
        ] == 504
        got = self._histograms(bins, node, channels, 4)
        want = _scatter_histogram(bins, node, channels, 4, self.BINS)
        if kind == "counts":
            assert np.array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)

    @pytest.mark.parametrize("kind", ["counts", "gradients"])
    def test_same_under_vmap_over_two_trees(self, rng, monkeypatch, kind):
        """The forest's shape: one binned matrix, a node index and a
        channel operand a tree."""
        bins, node, channels = self._inputs(rng, kind, 8)
        _, node_2, channels_2 = self._inputs(rng, kind, 8)
        _set_hist_geometry(monkeypatch, self.TILE, self.BLOCK)
        both = np.asarray(jax.vmap(
            lambda n, c: trees._level_histograms(
                jnp.asarray(bins), n, c, 8, self.BINS
            )
        )(jnp.stack([node, node_2]), jnp.stack([channels, channels_2])))
        for got, (n, c) in zip(both, [(node, channels), (node_2, channels_2)]):
            alone = self._histograms(bins, n, c, 8)
            want = _scatter_histogram(bins, n, c, 8, self.BINS)
            if kind == "counts":
                assert np.array_equal(got, want)
                assert np.array_equal(got, alone)
            else:
                np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
                np.testing.assert_allclose(got, alone, atol=1e-5, rtol=0)

    @pytest.mark.parametrize("data, model", [(8, 1), (4, 2)])
    @pytest.mark.parametrize("kind", ["counts", "gradients"])
    def test_same_on_a_row_sharded_mesh_with_one_reduction_a_block(
        self, rng, monkeypatch, kind, data, model
    ):
        """Rows sharded over ``data``: the tiles are cut inside each row
        shard (no gather of the matrix, the node index or the channels)
        and the partial histograms meet in one all-reduce a feature
        block, after the tile loop."""
        from learningorchestra_tpu.parallel.mesh import make_mesh
        from learningorchestra_tpu.parallel.sharding import row_sharded

        bins, node, channels = self._inputs(rng, kind, 4)
        want = _scatter_histogram(bins, node, channels, 4, self.BINS)
        _set_hist_geometry(monkeypatch, self.TILE, self.BLOCK)
        sharding = row_sharded(make_mesh(data=data, model=model))
        placed = [jax.device_put(a, sharding) for a in (bins, node, channels)]
        program = jax.jit(
            lambda b, n, c: trees._level_histograms(b, n, c, 4, self.BINS)
        )
        text = program.lower(*placed).compile().as_text()
        assert "all-gather" not in text and "all-to-all" not in text
        assert "collective-permute" not in text
        assert len(re.findall(r" all-reduce(?:-start)?\(", text)) == 1
        got = np.asarray(program(*placed))
        if kind == "counts":
            assert np.array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


class TestSplitBf16:
    """The channel operand as three bfloat16 pieces: their float32 sum
    is the float32 value again, so a one-pass bfloat16 contraction
    against a 0/1 indicator loses nothing."""

    PLANTED = np.array(
        [
            1e-6, -1e-6,            # the floor of the booster's h
            1e30, -1e30, 3.0e38,
            0.0, -0.0, 1.0, -1.0, 1.0 / 3.0, np.pi, 0.1, 16777215.0,
            1.0 + 2.0**-23, 1.0 - 2.0**-24, 255.99998, 2.0**-100,
            1.17549435e-38,         # the smallest normal float32
        ],
        dtype=np.float32,
    )

    def _sum(self, x):
        pieces = trees._split_bf16(jnp.asarray(x))
        assert all(piece.dtype == jnp.bfloat16 for piece in pieces)
        hi, mid, lo = (np.asarray(piece.astype(jnp.float32)) for piece in pieces)
        return (hi + mid) + lo

    def test_planted_values_come_back_exactly(self):
        back = self._sum(self.PLANTED)
        assert back.dtype == np.float32
        assert np.array_equal(back, self.PLANTED)

    def test_random_values_of_every_magnitude_come_back_exactly(self, rng):
        x = (
            rng.standard_normal(20000) * 10.0 ** rng.uniform(-24, 30, 20000)
        ).astype(np.float32)
        assert np.abs(x).min() > 2.0**-100
        assert np.array_equal(self._sum(x), x)

    def test_the_pieces_are_cut_from_the_bits_not_by_a_cast_and_back(self):
        """Inside a fused TPU program a bfloat16 intermediate lives in a
        float32 register, so ``x - x.astype(bfloat16)`` is zero there
        and a split made that way carries eight bits on the chip while
        passing every test on the CPU (PERF.md §6, PR 35). The pieces
        are masked out of the float32 pattern, and each cast at the end
        is of a value bfloat16 holds exactly."""
        text = str(jax.make_jaxpr(trees._split_bf16)(jnp.asarray(self.PLANTED)))
        assert text.count(" and ") == 2 and "bitcast_convert_type" in text
        # no float32 value is made from a bfloat16 one
        assert "convert_element_type[new_dtype=float32" not in text

    def test_subnormals_lose_no_more_than_the_smallest_normal(self):
        """A piece under the smallest normal float32 (2^-126) may be
        flushed to zero, on the chip as on the CPU: a subnormal value,
        or the low piece of a value under 2^-102, comes back to within
        that, which no float32 sum of sound gradients can see."""
        x = np.array(
            [1e-40, -3e-39, 1.4e-45, 5.9e-39, 2.1941396e-33, 3.3e-33],
            np.float32,
        )
        assert np.all(np.abs(self._sum(x) - x) <= 2.0**-126)


def _spans_of(work):
    """Every span ``work`` (a fit, a whole build) leaves in an active
    trace, at any depth."""
    from learningorchestra_tpu.telemetry import tracing

    trace = tracing.Trace(name="fit")
    with tracing.activate(trace):
        work()
    found, pending = [], list(trace.as_dict()["spans"])
    while pending:
        span = pending.pop()
        pending += span["children"]
        found.append(span)
    return found


def _enqueue_spans(work):
    return [span for span in _spans_of(work) if span["name"] == "fit:enqueue"]


class TestHistBlockPlan:
    """The tile and block plan the fits stamp on ``fit:enqueue`` is the
    one the contraction runs by, at the three benchmark cells' padded
    shapes."""

    @pytest.mark.parametrize(
        "rows, features, tile, block, blocks, indicator_bytes",
        [
            (163840, 2000, 16384, 125, 16, 131072000),   # epsilon-500k
            (8388608, 28, 16384, 28, 1, 29360128),       # higgs-11m
            (524288, 700, 16384, 100, 7, 104857600),     # expo-onehot-700
        ],
    )
    def test_figures_at_the_cells_shapes(
        self, rows, features, tile, block, blocks, indicator_bytes
    ):
        assert trees.hist_block_plan(rows, features, 32) == {
            "hist_tile_rows": tile,
            "hist_block_features": block,
            "hist_blocks": blocks,
            "hist_indicator_bytes": indicator_bytes,
        }
        # the bucketed row counts divide into whole tiles: no padding
        assert rows % tile == 0

    @pytest.mark.parametrize(
        "rows, tile",
        [(1, 8), (300, 304), (3072, 3072), (16384, 16384), (16385, 8200),
         (1000003, 16136)],
    )
    def test_a_tile_is_every_row_groups_equal_share_of_a_step(self, rows, tile):
        plan = trees.hist_block_plan(rows, 28, 32)
        assert plan["hist_tile_rows"] == tile
        assert tile % trees._HIST_ROW_GROUPS == 0
        steps = -(-rows // tile)
        assert 0 <= steps * tile - rows < trees._HIST_ROW_GROUPS * steps
        assert tile <= trees._HIST_TILE_ROWS + trees._HIST_ROW_GROUPS

    @pytest.mark.parametrize(
        "make",
        [
            lambda: DecisionTreeClassifier(max_depth=2),
            lambda: RandomForestClassifier(num_trees=2, max_depth=2),
            lambda: GBTClassifier(rounds=2, max_depth=2),
        ],
        ids=["dt", "rf", "gb"],
    )
    def test_the_fits_stamp_it_on_their_enqueue_span(self, rng, monkeypatch, make):
        X = rng.normal(size=(300, 40)).astype(np.float32)
        y = (X[:, 3] + X[:, 31] > 0).astype(np.int32)
        classifier = make()
        padded = prepare_xy(X, y, classifier.mesh)[0].shape[0]
        # 10 features a block, 4 blocks a level, five tiles, at this
        # small size
        _set_hist_geometry(monkeypatch, padded // 5, 10)
        (span,) = _enqueue_spans(lambda: classifier.fit(X, y))
        plan = trees.hist_block_plan(padded, 40, 32)
        assert plan["hist_block_features"] == 10 and plan["hist_blocks"] == 4
        assert plan["hist_tile_rows"] == padded // 5
        assert {k: span["meta"][k] for k in plan} == plan
        assert ("subset_k" in span["meta"]) == isinstance(
            classifier, RandomForestClassifier
        )


class TestForestFeatureSubsets:
    @pytest.mark.parametrize(
        "features, subset_k", [(2000, 45), (400, 20), (28, 6)]
    )
    def test_subset_k_is_the_ceiling_of_the_square_root(
        self, rng, features, subset_k
    ):
        X = rng.normal(size=(64, features)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        (span,) = _enqueue_spans(
            lambda: RandomForestClassifier(num_trees=1, max_depth=1).fit(X, y)
        )
        assert span["meta"]["subset_k"] == subset_k

    def test_no_node_splits_outside_its_drawn_subset(self, rng):
        nodes, features, max_bins, subset_k = 16, 2000, 32, 45
        gain = jnp.asarray(rng.random((nodes, features, max_bins)), jnp.float32)
        key = jax.random.key(11)
        feature, bin_index = trees._select_splits(gain, key, subset_k)
        # the draw, again: the subset_k smallest of a uniform score a column
        scores = np.asarray(jax.random.uniform(key, (nodes, features)))
        allowed = scores <= np.sort(scores, axis=1)[:, subset_k - 1 : subset_k]
        assert (allowed.sum(axis=1) == subset_k).all()
        feature, bin_index = np.asarray(feature), np.asarray(bin_index)
        assert allowed[np.arange(nodes), feature].all()
        # and inside the subset it is the best candidate
        inside = np.where(allowed[:, :, None], np.asarray(gain), -np.inf)
        assert np.array_equal(
            inside.reshape(nodes, -1).argmax(axis=1), feature * max_bins + bin_index
        )
        # without a subset the best of all 64,000 candidates wins
        free, _ = trees._select_splits(gain, None, None)
        assert not allowed[np.arange(nodes), np.asarray(free)].all()


BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"
)


class _Harness:
    """The two calls the benchmark's comparison makes on a system, on a
    store of this test's own."""

    def __init__(self):
        from lib import system

        from learningorchestra_tpu.core.store import InMemoryStore

        self.store = InMemoryStore()
        self._system = system.System

    def stored(self, collection, fields):
        return self._system.stored(self, collection, fields)

    def write_dataset(self, name, columns, labels, fields):
        self._system.write_dataset(self, name, columns, labels, fields)


def _audited_build(cell, dataset, train, test, seed, block_cap, models_dir):
    """One build of ``cell``'s classifiers through ``build_model`` -
    store, documented preprocessor, write-back, checkpoints - on
    ``train`` + ``test`` rows from the cell's maker with ``dataset``'s
    keys changed, the level histograms cut into tiles and blocks by
    ``block_cap`` (the rows of a tile, the columns of a block), and what
    was published
    and stored held against the benchmark's plain float64 reference."""
    patch = pytest.MonkeyPatch()
    patch.syspath_prepend(BENCH)
    patch.setenv("LO_RESUME", "0")
    _set_hist_geometry(patch, *block_cap)
    try:
        from lib import cells, correct, system

        from learningorchestra_tpu.ml.builder import build_model

        config = json.loads(json.dumps(cells.Cell(cell).config))
        config["dataset"].update(dataset)
        ref = cells.load_module("reference", "classifiers")
        maker = cells.load_module("datasets", config["dataset"]["maker"])
        columns, labels, fields = maker.make(config["dataset"], seed, train + test)
        harness, n = _Harness(), train
        harness.write_dataset("cell_train", [c[:n] for c in columns], labels[:n], fields)
        harness.write_dataset("cell_test", [c[n:] for c in columns], labels[n:], fields)
        posted = time.time()
        spans = _spans_of(lambda: build_model(
            harness.store, "cell_train", "cell_test", system.PREPROCESSOR,
            config["classifiers"], models_dir=models_dir,
        ))
        X = ref.as_matrix(columns, dtype=np.float32)
        comparison = correct.Comparison(
            ref, config, seed, X[:n], labels[:n], X[n:], labels[n:]
        )
        outputs, violations = correct.read_build(
            harness, ref, config, {"test": "cell_test"}, models_dir, posted,
            labels[n:], comparison.sample,
        )
        comparison.compare(outputs)
        # the forest's two sampling numbers, for a plain float64
        # forest grown on the same rows by the reference itself
        plain = comparison.audit_rf(ref.grow_forest(
            comparison.bins, comparison.thresholds, labels[:n],
            int(config["hyper"]["max_bins"]), int(config["hyper"]["max_depth"]),
            trees=int(config["correct"]["rf_trees_checked"]), seed=seed,
        ))
        return {
            "numbers": comparison.numbers, "limits": config["limits"],
            "violations": violations, "plain": plain, "train": X[:n],
            "spans": {
                kind: [span for span in spans if span["name"] == f"fit:{kind}"]
                for kind in ("enqueue", "thresholds")
            },
        }
    finally:
        patch.undo()


class TestWideBuildAgainstThePlainReference:
    """``dt`` + ``rf`` + ``gb`` built through ``build_model`` - store,
    documented preprocessor, write-back, checkpoints - on a wide dense
    table from the benchmark's second maker at a small size (3,000 +
    1,000 rows x 400 columns: five blocks of 80 once the cap is shrunk),
    and what was published and stored held against the benchmark's plain
    float64 reference inside the limits of ``epsilon-500k`` (PERF.md §4;
    the cell itself runs 163,840 + 100,000 x 2,000 on the chip)."""

    @pytest.fixture(scope="class")
    def audit(self, tmp_path_factory):
        # the wide path: several blocks of several columns each, three
        # row tiles (3,000 rows pad to the 3,072 bucket)
        return _audited_build(
            "epsilon-500k.build3", {"features": 400}, 3000, 1000, 2147483777,
            (1024, 80), str(tmp_path_factory.mktemp("models")),
        )

    def test_nothing_is_missing_and_the_wide_path_ran(self, audit):
        assert audit["violations"] == []
        assert len(audit["spans"]["enqueue"]) == 3
        for span in audit["spans"]["enqueue"]:
            assert span["meta"]["hist_block_features"] == 80
            assert span["meta"]["hist_blocks"] == 5

    @pytest.mark.parametrize("number", [
        "tree_pred_gap", "dt_leaf_gap", "dt_split_gap", "dt_loss_gap",
        "gb_leaf_gap", "gb_split_gap", "ensemble_loss_gap",
    ])
    def test_inside_the_configurations_limit(self, audit, number):
        assert audit["numbers"][number] <= audit["limits"][number]

    @pytest.mark.parametrize("number", ["rf_leaf_z", "rf_split_gap"])
    def test_the_forest_reads_as_a_plain_float64_forest_on_these_rows(
        self, audit, number
    ):
        """A forest's leaves and thresholds carry its own bootstrap and
        its own choice among a node's candidates, which at 3,000 rows
        reads several times what it reads at the cell's 163,840 (the
        limits are the chip's): here the two numbers are held to what
        the reference's own forest reads on the same rows."""
        assert audit["numbers"][number] <= 1.25 * audit["plain"][number]


class TestOneHotBuildAgainstThePlainReference:
    """``dt`` + ``rf`` + ``gb`` + ``nb`` built through ``build_model`` -
    store, documented preprocessor, write-back, checkpoints - on a
    one-hot coded table from the benchmark's third maker at a small size
    (6,000 + 600 rows x 280 columns: 278 indicators of the six groups
    with 8 carriers and 110 airports, ten blocks of 28 once the cap is
    shrunk), and what was published and stored held against the
    benchmark's plain float64 reference inside the limits of
    ``expo-onehot-700`` (PERF.md §4; the cell itself runs 524,288 +
    52,429 x 700 on the chip)."""

    FEATURES = 280

    @pytest.fixture(scope="class")
    def audit(self, tmp_path_factory):
        # 28 columns a block, three row tiles (6,000 rows pad to the
        # 6,144 bucket)
        found = _audited_build(
            "expo-onehot-700.build4", {"carriers": 8, "airports": 110}, 6000, 600,
            2147483999, (2048, 28), str(tmp_path_factory.mktemp("models")),
        )
        found["thresholds"] = make_thresholds(np.ascontiguousarray(found["train"]), 32)
        return found

    def test_nothing_is_missing_and_the_blocked_path_ran(self, audit):
        assert audit["violations"] == []
        # naive Bayes' fit has an enqueue span too, with no block plan
        plans = [s["meta"] for s in audit["spans"]["enqueue"] if s.get("meta")]
        assert len(audit["spans"]["enqueue"]) == 4 and len(plans) == 3
        for plan in plans:
            assert plan["hist_block_features"] == 28 and plan["hist_blocks"] == 10
        assert sum("subset_k" in plan for plan in plans) == 1
        assert next(p["subset_k"] for p in plans if "subset_k" in p) == 17

    def test_the_table_is_what_the_cell_is_there_for(self, audit):
        """Indicator columns whose level is rare (all 31 thresholds 0),
        common (0 and 1) or absent from the training rows (a constant
        column), and two integer columns with thresholds of their own."""
        X, thresholds = audit["train"], audit["thresholds"]
        share = X[:, :-2].mean(axis=0)
        assert set(np.unique(X[:, :-2])) == {0.0, 1.0}
        assert (share == 0).sum() >= 5 and (share >= 1 / 32).sum() >= 20
        assert ((share > 0) & (share < 1 / 32)).sum() >= 100
        assert (thresholds[:-2][share < 1 / 32] == 0).all()
        assert min(len(np.unique(row)) for row in thresholds[-2:]) > 25

    def test_the_span_of_the_pass_counts_the_distinct_thresholds(self, audit):
        spans = audit["spans"]["thresholds"]
        assert sorted(s["meta"]["passes"] for s in spans) == [0, 0, 1]
        (ran,) = [s for s in spans if s["meta"]["passes"] == 1]
        counted = sum(len(np.unique(row)) for row in audit["thresholds"])
        assert ran["meta"]["distinct_thresholds"] == counted
        assert ran["meta"]["features"] == self.FEATURES and ran["meta"]["bins"] == 32
        # a sliver of the 280 x 31 slots: most indicators have one
        assert self.FEATURES <= counted < 2 * self.FEATURES + 62
        assert all(
            "distinct_thresholds" not in s["meta"] for s in spans if s["meta"]["passes"] == 0
        )

    @pytest.mark.parametrize("number", [
        "tree_pred_gap", "dt_leaf_gap", "dt_split_gap", "dt_loss_gap",
        "gb_leaf_gap", "gb_split_gap", "ensemble_loss_gap",
        "nb_pred_gap", "nb_theta_gap", "nb_prior_gap",
    ])
    def test_inside_the_configurations_limit(self, audit, number):
        assert audit["numbers"][number] <= audit["limits"][number]

    @pytest.mark.parametrize("number", ["rf_leaf_z", "rf_split_gap"])
    def test_the_forest_reads_as_a_plain_float64_forest_on_these_rows(
        self, audit, number
    ):
        """As in the wide build: the forest's two sampling numbers fall
        with the rows, so here they are held to what the reference's own
        forest reads on the same 6,000 rows."""
        assert audit["numbers"][number] <= 1.25 * audit["plain"][number]


class TestNoPositivePredicted:
    """A table whose every feature column is an indicator of a level
    that no row holds - all zeros, as the rarest levels of a one-hot
    coded table are - with 19 % positive labels: no tree can split, the
    booster keeps its prior and naive Bayes its class shares, so every
    classifier predicts the negative class on every row. The stored
    accuracy is the negative share and the stored F1 the weighted F1 of
    those labels, with a class nobody predicted counted as 0."""

    CLASSIFIERS = ["dt", "rf", "gb", "nb"]

    @pytest.fixture(scope="class")
    def built(self, tmp_path_factory):
        patch = pytest.MonkeyPatch()
        patch.syspath_prepend(BENCH)
        patch.setenv("LO_RESUME", "0")
        try:
            from lib import cells, correct, system

            from learningorchestra_tpu.ml.builder import build_model

            ref = cells.load_module("reference", "classifiers")
            rng = np.random.default_rng(19)
            labels = (rng.random(1100) < 0.19).astype(np.int64)
            columns = [np.zeros(1100, np.float32) for _ in range(5)]
            fields = [f"Origin_{j}" for j in range(5)]
            harness = _Harness()
            harness.write_dataset("flat_train", [c[:1000] for c in columns], labels[:1000], fields)
            harness.write_dataset("flat_test", [c[1000:] for c in columns], labels[1000:], fields)
            models_dir = str(tmp_path_factory.mktemp("models"))
            posted = time.time()
            build_model(
                harness.store, "flat_train", "flat_test", system.PREPROCESSOR,
                self.CLASSIFIERS, models_dir=models_dir,
            )
            outputs, violations = correct.read_build(
                harness, ref, {"classifiers": self.CLASSIFIERS}, {"test": "flat_test"},
                models_dir, posted, labels[1000:], np.arange(100),
            )
            rows = {
                clf: (
                    harness.store.find_one(f"flat_test_prediction_{clf}", {"_id": 0}),
                    harness.stored(f"flat_test_prediction_{clf}", ["prediction"])["prediction"],
                )
                for clf in self.CLASSIFIERS
            }
            return {"violations": violations, "rows": rows, "truth": labels[1000:], "ref": ref}
        finally:
            patch.undo()

    @pytest.mark.parametrize("clf", CLASSIFIERS)
    def test_stored_accuracy_and_f1_are_the_stored_labels(self, built, clf):
        assert not [v for v in built["violations"] if v.startswith(clf)]
        meta, predicted = built["rows"][clf]
        truth = built["truth"]
        assert len(predicted) == 100 and not predicted.any()
        negative = float((truth == 0).mean())
        assert 0.7 < negative < 0.9
        assert float(meta["accuracy"]) == pytest.approx(negative, abs=1e-6)
        # weighted F1: the negative class's F1 by its share, the positive
        # class's (precision 0 / 0, recall 0) counted as 0
        f1 = negative * (2 * negative / (1 + negative))
        assert float(meta["F1"]) == pytest.approx(f1, abs=1e-6)
        accuracy, f1_ref = built["ref"].accuracy_f1(predicted.astype(np.int64), truth)
        assert float(meta["accuracy"]) == pytest.approx(accuracy, abs=1e-6)
        assert float(meta["F1"]) == pytest.approx(f1_ref, abs=1e-6)
