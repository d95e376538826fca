"""Estimators vs sklearn/numpy oracles: evaluation, logistic, naive bayes."""

import numpy as np
import pytest
import sklearn.linear_model
import sklearn.metrics
import sklearn.naive_bayes

from learningorchestra_tpu.ml import (
    LogisticRegression,
    NaiveBayes,
    accuracy_score,
    f1_score,
)


class TestEarlyExitPlateau:
    """The tol early-exit must stop on a genuine plateau and ONLY on
    one: a single floor-step Armijo iteration (one tiny loss delta
    inside an otherwise-descending run) used to satisfy the check and
    stop a fit mid-descent (ADVICE r5)."""

    def test_plateaued_requires_every_delta_and_the_total(self):
        from learningorchestra_tpu.ml.logistic import _plateaued

        tol = 1e-6
        # genuine plateau: stop
        assert _plateaued([0.5, 0.5, 0.5, 0.5], tol, 4)
        # momentary plateau (one tiny delta mid-descent): keep going
        assert not _plateaued([1.0, 0.9999999, 0.99, 0.98], tol, 4)
        assert not _plateaued([1.0, 0.99, 0.9899999, 0.97], tol, 4)
        # too little history: keep going
        assert not _plateaued([0.5, 0.5], tol, 4)
        # slow steady descent whose per-step deltas all sneak under a
        # loose tol but whose window total does not: keep going
        loose = 1.1e-2
        assert not _plateaued([1.03, 1.02, 1.01, 1.00], loose, 4)

    def _scripted_fit(self, monkeypatch, value_at):
        """Run logistic._fit with _fit_segment replaced by a scripted
        loss curve; returns how many segments were consumed."""
        from learningorchestra_tpu.ml import logistic

        calls = {"segments": 0, "cursor": 0}

        def fake_segment(params, opt_state, X, y, mask, iters, l2, fresh=True):
            calls["segments"] += 1
            start = calls["cursor"]
            calls["cursor"] += iters
            losses = np.asarray(
                [value_at(start + k) for k in range(iters)], np.float32
            )
            return params, opt_state, losses

        monkeypatch.setattr(logistic, "_fit_segment", fake_segment)
        X = np.zeros((4, 2), np.float32)
        y = np.zeros((4,), np.int32)
        logistic._fit(
            {"w": np.zeros((2, 2))},
            X,
            y,
            np.ones((4,), np.float32),
            max_iter=100,
            l2=0.0,
        )
        return calls["segments"]

    def test_momentary_plateau_does_not_terminate(self, monkeypatch):
        # strictly descending except ONE flat step at iteration 31
        def value_at(i):
            effective = i if i < 31 else i - 1  # v(31) == v(30)
            return 100.0 - effective * 0.1

        # all four 25-iteration segments run: no early exit
        assert self._scripted_fit(monkeypatch, value_at) == 4

    def test_genuine_plateau_terminates_early(self, monkeypatch):
        def value_at(i):
            return max(1.0, 100.0 - i * 2.0)  # flat from iteration 50

        # the segment covering iterations 50..74 ends on a real plateau
        assert self._scripted_fit(monkeypatch, value_at) == 3


@pytest.fixture()
def blobs(rng):
    """Linearly separable-ish 3-class data."""
    n, f, c = 600, 5, 3
    centers = rng.normal(size=(c, f)) * 3
    y = rng.integers(0, c, size=n)
    X = centers[y] + rng.normal(size=(n, f))
    return X, y


class TestEvaluation:
    def test_accuracy_matches_sklearn(self, rng):
        y_true = rng.integers(0, 4, size=500)
        y_pred = rng.integers(0, 4, size=500)
        assert accuracy_score(y_true, y_pred) == pytest.approx(
            sklearn.metrics.accuracy_score(y_true, y_pred)
        )

    def test_weighted_f1_matches_sklearn(self, rng):
        y_true = rng.integers(0, 4, size=500)
        y_pred = rng.integers(0, 4, size=500)
        assert f1_score(y_true, y_pred) == pytest.approx(
            sklearn.metrics.f1_score(y_true, y_pred, average="weighted"), abs=1e-6
        )

    def test_perfect_prediction(self):
        y = np.array([0, 1, 2, 1, 0])
        assert accuracy_score(y, y) == 1.0
        assert f1_score(y, y) == 1.0


class TestLogisticRegression:
    def test_separates_blobs(self, blobs):
        X, y = blobs
        model = LogisticRegression(max_iter=50).fit(X, y)
        assert accuracy_score(y, model.predict(X)) > 0.95

    def test_agrees_with_sklearn(self, blobs):
        X, y = blobs
        ours = LogisticRegression(max_iter=100).fit(X, y).predict(X)
        theirs = (
            sklearn.linear_model.LogisticRegression(C=1e6, max_iter=1000)
            .fit(X, y)
            .predict(X)
        )
        assert np.mean(ours == theirs) > 0.98

    def test_proba_shape_and_normalization(self, blobs):
        X, y = blobs
        probs = LogisticRegression(max_iter=20).fit(X, y).predict_proba(X)
        assert probs.shape == (len(X), 3)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)

    def test_binary(self, rng):
        X = rng.normal(size=(300, 4))
        y = (X[:, 0] + X[:, 1] > 0).astype(int)
        model = LogisticRegression(max_iter=50).fit(X, y)
        assert accuracy_score(y, model.predict(X)) > 0.95


class TestNaiveBayes:
    def test_matches_sklearn_multinomial(self, rng):
        X = rng.integers(0, 20, size=(400, 8)).astype(float)
        y = rng.integers(0, 3, size=400)
        ours = NaiveBayes().fit(X, y)
        theirs = sklearn.naive_bayes.MultinomialNB(alpha=1.0).fit(X, y)
        assert np.array_equal(ours.predict(X), theirs.predict(X))
        np.testing.assert_allclose(
            ours.predict_proba(X), theirs.predict_proba(X), atol=1e-4
        )

    def test_rejects_negative_features(self, rng):
        X = rng.normal(size=(50, 3))
        y = rng.integers(0, 2, size=50)
        with pytest.raises(ValueError):
            NaiveBayes().fit(X, y)

    def test_padding_does_not_bias_fit(self, rng):
        # 7 rows on an 8-device mesh → 1 padding row; priors must use
        # only real rows.
        X = rng.integers(0, 5, size=(7, 3)).astype(float)
        y = np.array([0, 0, 0, 0, 1, 1, 1])
        ours = NaiveBayes().fit(X, y)
        theirs = sklearn.naive_bayes.MultinomialNB(alpha=1.0).fit(X, y)
        np.testing.assert_allclose(
            ours.predict_proba(X), theirs.predict_proba(X), atol=1e-4
        )


def _planted_problem(seed=3, rows=512, features=5):
    """Standardised-looking rows, labels from a noisy linear rule."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, features)).astype(np.float32)
    y = (X @ rng.normal(size=features) + rng.normal(size=rows) > 0).astype(np.int32)
    return X, y


class TestMarginLineSearch:
    """The line search's trial steps are taken on the carried margins
    (f(Z + t·M)); the parent evaluated each trial from the parameters.
    One iteration is planted so that the Armijo loop rejects k steps:
    the curvature memory holds one pair (s, y) with s = alpha·y, which
    makes the two-loop direction exactly -alpha·g, and a larger alpha
    overshoots further."""

    @staticmethod
    def _state(params, alpha):
        import jax
        import jax.numpy as jnp

        from learningorchestra_tpu.ml import logistic

        state = logistic._lbfgs_state(params)
        y_pair = jax.tree.map(
            lambda p: jnp.linspace(0.5, 1.5, p.size, dtype=jnp.float32)
            .reshape(p.shape),
            params,
        )
        s_pair = jax.tree.map(lambda v: alpha * v, y_pair)
        sy = float(logistic._tree_dot(s_pair, y_pair))
        return {
            **state,
            "S": jax.tree.map(lambda h, v: h.at[0].set(v), state["S"], s_pair),
            "Y": jax.tree.map(lambda h, v: h.at[0].set(v), state["Y"], y_pair),
            "rho": state["rho"].at[0].set(1.0 / sy),
            "head": jnp.int32(1),
            "filled": jnp.int32(1),
        }

    @staticmethod
    def _per_trial_iteration(params, state, X, y, mask, l2):
        """One iteration the parent's way: every trial is a full loss."""
        import jax

        from learningorchestra_tpu.ml import logistic

        loss = lambda p: logistic._loss_fn(p, X, y, mask, l2)  # noqa: E731
        value, grad = jax.value_and_grad(loss)(params)
        direction = logistic._two_loop({**state, "value": value, "grad": grad})
        slope = float(logistic._tree_dot(grad, direction))
        assert slope < 0
        rejected = 0
        step = 1.0 / (1 << logistic._BACKTRACK_STEPS)
        for k in range(logistic._BACKTRACK_STEPS):
            t = 0.5 ** k
            trial = loss(logistic._tree_axpy(t, direction, params))
            if trial <= value + logistic._ARMIJO_C1 * t * slope:
                step = t
                break
            rejected += 1
        return logistic._tree_axpy(step, direction, params), step, rejected

    @pytest.mark.parametrize("rejections", [0, 1, 2, 3, 4])
    def test_same_step_and_params_as_the_per_trial_recompute(self, rejections):
        import jax.numpy as jnp

        from learningorchestra_tpu.ml import logistic

        X_np, y_np = _planted_problem()
        X, y = jnp.asarray(X_np), jnp.asarray(y_np)
        mask = jnp.ones(len(X_np), jnp.float32)
        l2 = jnp.float32(0.01)
        params = {
            "w": jnp.full((5, 2), 0.05, jnp.float32).at[:, 1].set(-0.05),
            "b": jnp.asarray([0.1, -0.1], jnp.float32),
        }
        found = None
        for alpha in np.geomspace(0.5, 1e5, 60):
            state = self._state(params, float(alpha))
            want, step, rejected = self._per_trial_iteration(
                params, state, X, y, mask, l2
            )
            if rejected == rejections:
                found = state
                break
        assert found is not None, f"no planted alpha rejects {rejections}"
        got, _, _ = logistic._fit_segment(params, found, X, y, mask, 1, l2)
        # the accepted step, read back from the move along -alpha·g
        move = np.asarray(got["w"] - params["w"]).ravel()
        planned = np.asarray(want["w"] - params["w"]).ravel() / step
        assert np.dot(move, planned) / np.dot(planned, planned) == (
            pytest.approx(step, rel=1e-5)
        )
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(got[leaf]), np.asarray(want[leaf]), atol=1e-5
            )

    def test_armijo_step_reports_its_trials(self):
        import jax.numpy as jnp

        from learningorchestra_tpu.ml import logistic

        # f(t) = t^2 - t from f(0) = 0, slope -1: accepts t = 1/2 after
        # rejecting t = 1 (1 - 1 = 0 > -1e-4)
        step, trials = logistic._armijo_step(
            lambda t: t * t - t, jnp.float32(0.0), jnp.float32(-1.0)
        )
        assert float(step) == 0.5 and int(trials) == 2
        # never acceptable: the floor after every trial
        step, trials = logistic._armijo_step(
            lambda t: t + 1.0, jnp.float32(0.0), jnp.float32(-1.0)
        )
        assert float(step) == 1.0 / 16 and int(trials) == 4


def _planted_columns(kind, rows=3000, seed=7):
    rng = np.random.default_rng(seed)
    base = rng.normal(size=rows)
    if kind == "offset":  # far from zero, spread 1
        col = 1e4 + base
    elif kind == "constant":
        col = np.full(rows, 0.1)
    elif kind == "denormals":  # normal values with denormals planted
        col = 1.0 + 0.5 * base
        col[::3] = 1e-40
    else:
        col = 3.0 * base - 2.0
    other = rng.normal(size=rows)
    return np.stack([col, other], axis=1).astype(np.float32)


class TestDeviceStandardization:
    """The device recipe (one program, float32) against float64 stats of
    the same float32 matrix on planted columns, with padding rows."""

    @pytest.mark.parametrize(
        "kind", ["ordinary", "offset", "constant", "denormals"]
    )
    def test_mean_and_scale_match_float64(self, kind):
        from learningorchestra_tpu.ml import logistic
        from learningorchestra_tpu.ml.base import prepare_xy, resolve_mesh

        X = _planted_columns(kind, rows=3001)  # padded to a bucket
        X_dev, _, mask = prepare_xy(X, None, resolve_mesh(None))
        assert X_dev.shape[0] > len(X)
        X_std, mean, scale = logistic._standardize(X_dev, mask)
        want_mean = X.astype(np.float64).mean(axis=0)
        want_std = X.astype(np.float64).std(axis=0)
        want_scale = np.where(want_std > 0, want_std, 1.0)
        assert mean.dtype == scale.dtype == np.float32
        # the mean within 1e-6 of its own size or of the spread,
        # whichever is larger (a mean near 0 has no relative precision)
        mean_error = np.abs(np.asarray(mean, np.float64) - want_mean)
        assert np.all(
            mean_error <= 1e-6 * np.maximum(np.abs(want_mean), want_scale)
        ), mean_error
        np.testing.assert_allclose(np.asarray(scale), want_scale, rtol=1e-6)
        std = np.asarray(X_std)
        assert np.all(std[len(X):] == 0)  # padding rows zeroed
        if kind == "constant":
            assert float(scale[0]) == 1.0 and float(mean[0]) == np.float32(0.1)
            assert np.all(std[:, 0] == 0)
        else:
            np.testing.assert_allclose(std[: len(X)].mean(axis=0), 0, atol=1e-4)
            np.testing.assert_allclose(std[: len(X)].std(axis=0), 1, rtol=1e-4)

    def test_fit_donates_its_copy_and_stamps_its_passes(self):
        from learningorchestra_tpu.ml import logistic
        from learningorchestra_tpu.telemetry import tracing

        X, y = _planted_problem(rows=300)
        trace = tracing.Trace(name="lr")
        with tracing.activate(trace), tracing.span("phase:fit"):
            logistic.LogisticRegression(max_iter=10, tol=0.0).fit(X, y)
        (fit,) = trace.as_dict()["spans"]
        names = [child["name"] for child in fit["children"]]
        # a lone fit makes its own input: one copy, then standardised
        # into a buffer of its own (the copy is not donated: it is the
        # input's, as a build's is shared)
        assert names.index("h2d:train") < names.index("fit:standardize")
        (copy,) = [c for c in fit["children"] if c["name"] == "h2d:train"]
        assert copy["meta"]["copies"] == 1
        # one segment: its seed's margins and gradient, then ten
        # iterations of two passes each
        assert fit["meta"]["lbfgs_matrix_passes"] == 2 + 2 * 10

    def test_fit_leaves_a_device_matrix_it_was_given(self):
        """The device copy of a TrainingInput is the build's (shared by
        its models): the fit scales it into a new buffer and never
        donates it."""
        from learningorchestra_tpu.ml import logistic
        from learningorchestra_tpu.ml.base import TrainingInput

        X, y = _planted_problem(rows=300)
        shared = TrainingInput(X, y)
        matrix, _ = shared.device()
        before = np.asarray(matrix.data)
        first = logistic.LogisticRegression(max_iter=10).fit(shared, y)
        assert not matrix.data.is_deleted()
        np.testing.assert_array_equal(np.asarray(matrix.data), before)
        # and it serves again: a second fit and a predict from it
        second = logistic.LogisticRegression(max_iter=10).fit(shared, y)
        np.testing.assert_array_equal(
            np.asarray(first.params["w"]), np.asarray(second.params["w"])
        )
        np.testing.assert_array_equal(
            second.predict(matrix), second.predict(X)
        )


class TestMatrixPassesDoNotFollowTheLineSearch:
    """Two problems whose line searches make different numbers of trial
    steps (the parent paid a pass over X for each) stamp the same
    ``lbfgs_matrix_passes``."""

    def test_two_seeds_same_passes_different_trials(self, monkeypatch):
        import jax

        from learningorchestra_tpu.ml import logistic
        from learningorchestra_tpu.telemetry import tracing

        trials_seen: list[int] = []
        armijo = logistic._armijo_step

        def counting(trial_loss, value, slope):
            step, trials = armijo(trial_loss, value, slope)
            jax.debug.callback(lambda k: trials_seen.append(int(k)), trials)
            return step, trials

        monkeypatch.setattr(logistic, "_armijo_step", counting)
        # a fresh program, so the counting line search is traced
        monkeypatch.setattr(
            logistic,
            "_fit_segment",
            jax.jit(
                logistic._fit_segment_impl,
                static_argnames=("iters", "fresh"),
            ),
        )
        rng = np.random.default_rng(1)
        X = rng.normal(size=(400, 4))
        noisy = (X[:, 0] + rng.normal(size=400) > 0).astype(np.int64)
        separable = (X[:, 0] > 0).astype(np.int64)  # weights grow: halvings
        passes, trials = [], []
        for y in (noisy, separable):
            trials_seen.clear()
            trace = tracing.Trace(name="lr")
            with tracing.activate(trace), tracing.span("phase:fit"):
                logistic.LogisticRegression(max_iter=20, tol=0.0).fit(X, y)
            jax.effects_barrier()
            (fit,) = trace.as_dict()["spans"]
            passes.append(fit["meta"]["lbfgs_matrix_passes"])
            trials.append(sum(trials_seen))
            assert len(trials_seen) == 20
        assert trials[0] != trials[1]
        assert passes[0] == passes[1] == 2 + 2 * 20


def _one_dot(p, s, X, y, m, iters, l2, fresh=True):
    return X @ p["w"]


def _no_dot_over_x(p, s, X, y, m, iters, l2, fresh=True):
    return p["w"] @ p["w"].T, X.sum()


def _dot_in_scan(p, s, X, y, m, iters, l2, fresh=True):
    import jax

    def body(w, _):
        return w + (X @ w).sum(), None

    return jax.lax.scan(body, p["w"], length=iters)[0]


def _dot_in_cond(p, s, X, y, m, iters, l2, fresh=True):
    import jax

    return jax.lax.cond(
        l2 > 0,
        lambda: (X @ p["w"]).sum() + (X.T @ X).sum(),
        lambda: (p["w"] ** 2).sum(),
    )


def _trial_passes_in_the_line_search(p, s, X, y, m, iters, l2, fresh=True):
    """The parent's line search: a whole loss over X for every trial."""
    import jax

    from learningorchestra_tpu.ml import logistic

    def body(w, _):
        params = {"w": w, "b": p["b"]}
        value = logistic._loss_fn(params, X, y, m, l2)
        t, _ = logistic._armijo_step(
            lambda t: logistic._loss_fn(
                {"w": w - t, "b": p["b"]}, X, y, m, l2
            ),
            value,
            -1.0,
        )
        return w - t, None

    return jax.lax.scan(body, p["w"], length=iters)[0]


class TestPassesAreCountedOffTheProgram:
    """``lbfgs_matrix_passes`` is read off the traced segment: each
    contraction over X, times the trips of the loops round it; a trial
    step that read X again would be counted."""

    @pytest.mark.parametrize(
        "program, want",
        [
            (_one_dot, 1),
            (_no_dot_over_x, 0),
            (_dot_in_scan, 3),
            (_dot_in_cond, 2),
            # a loss in the line search's loop: up to four trials
            (_trial_passes_in_the_line_search, 3 * (1 + 4)),
        ],
    )
    def test_counts(self, program, want):
        import jax.numpy as jnp

        from learningorchestra_tpu.ml import logistic

        import jax

        params = {
            "w": jnp.ones((4, 2), jnp.float32),
            "b": jnp.zeros(2, jnp.float32),
        }
        X = jnp.ones((16, 4), jnp.float32)
        y = jnp.zeros(16, jnp.int32)
        mask = jnp.ones(16, jnp.float32)
        segment = jax.jit(program, static_argnames=("iters", "fresh"))
        assert logistic._matrix_passes(
            segment, params, {}, X, y, mask, 3, jnp.float32(0.1), True
        ) == want
        # a plain function is not a program: nothing to count
        assert logistic._matrix_passes(
            program, params, {}, X, y, mask, 3, jnp.float32(0.1), True
        ) == 0

    @pytest.mark.parametrize(
        "fresh, want", [(True, 2 + 2 * 5), (False, 1 + 2 * 5)]
    )
    def test_the_segment_reads_x_twice_an_iteration(self, fresh, want):
        import jax.numpy as jnp

        from learningorchestra_tpu.ml import logistic

        params = {
            "w": jnp.zeros((4, 2), jnp.float32),
            "b": jnp.zeros(2, jnp.float32),
        }
        state = logistic._lbfgs_state(params)
        X = jnp.ones((16, 4), jnp.float32)
        y = jnp.zeros(16, jnp.int32)
        mask = jnp.ones(16, jnp.float32)
        assert logistic._matrix_passes(
            logistic._fit_segment, params, state, X, y, mask, 5,
            jnp.float32(0.0), fresh,
        ) == want


class TestOneRecipe:
    def test_solo_fit_and_sweep_member_share_the_scaler(self):
        """The sweep standardises each member with the solo fit's
        program: the same mean and scale for the same rows, though the
        two pad them to different row counts."""
        from learningorchestra_tpu.ml import sweep as lo_sweep
        from learningorchestra_tpu.ml.base import resolve_mesh
        from learningorchestra_tpu.sched.coalesce import Coalescer

        mesh = resolve_mesh(None)
        X = _planted_columns("offset", rows=700)
        X = np.concatenate([X, _planted_columns("ordinary", rows=700)], 1)
        y = (X[:, 1] > 0).astype(np.int64)
        key, payload = lo_sweep.prepare_member(
            "lr", X, y, X, y, [{"reg_param": 0.0}], mesh=mesh, max_iter=5
        )
        assert "mean" not in payload  # nothing computed on the host
        co = Coalescer(window_s=0.0, max_jobs=8)
        member = co.register(key, payload, lo_sweep.group_runner(mesh))
        swept = co.run_member(member)["params"][0]
        solo = LogisticRegression(max_iter=5, tol=0.0, mesh=mesh).fit(X, y)
        np.testing.assert_allclose(
            swept["mean"], np.asarray(solo.mean), rtol=1e-6,
            atol=1e-6 * float(np.asarray(solo.scale).min()),
        )
        np.testing.assert_allclose(
            swept["scale"], np.asarray(solo.scale), rtol=1e-6
        )


class TestMeshesAgree:
    @pytest.mark.parametrize("data,model", [(8, 1), (4, 2)])
    def test_sharded_fit_matches_one_device(self, data, model):
        import jax

        from learningorchestra_tpu.parallel.mesh import make_mesh

        X, y = _planted_problem(rows=1000, features=6)
        X = X * np.float32(3.0) + np.float32(50.0)
        one = make_mesh(data=1, model=1, devices=jax.devices()[:1])
        sharded = make_mesh(data=data, model=model)
        fits = [
            LogisticRegression(max_iter=30, tol=0.0, mesh=mesh).fit(X, y)
            for mesh in (one, sharded)
        ]
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(fits[0].params[leaf]),
                np.asarray(fits[1].params[leaf]),
                atol=1e-5,
            )
        np.testing.assert_allclose(
            fits[0].predict_proba(X), fits[1].predict_proba(X), atol=1e-5
        )


class TestNamedPrecision:
    """LR's contractions name exact float32: the backend's default
    matmul precision (one bfloat16 pass on a TPU) does not reach them."""

    @pytest.mark.parametrize("features", [6, 300])
    def test_fit_ignores_the_default_matmul_precision(self, features):
        import jax

        from learningorchestra_tpu.ml import logistic

        rng = np.random.default_rng(features)
        X = rng.normal(size=(400, features)).astype(np.float32)
        y = (X[:, 0] + 0.5 * rng.normal(size=400) > 0).astype(np.int64)
        probs = {}
        for setting in ("bfloat16", "highest"):
            with jax.default_matmul_precision(setting):
                model = LogisticRegression(max_iter=15, tol=0.0).fit(X, y)
                probs[setting] = model.predict_proba(X)
        np.testing.assert_allclose(
            probs["bfloat16"], probs["highest"], atol=1e-5
        )
        # and the programs say so: every dot of the segment (the
        # margins, the gradient, the two-loop's inner products) and of
        # the forward names HIGHEST
        params = {
            "w": np.zeros((features, 2), np.float32),
            "b": np.zeros((2,), np.float32),
        }
        with jax.default_matmul_precision("bfloat16"):
            jaxprs = [
                jax.make_jaxpr(
                    lambda p, s: logistic._fit_segment_impl(
                        p, s, X, y.astype(np.int32),
                        np.ones(400, np.float32), 2, np.float32(0),
                    )
                )(params, logistic._lbfgs_state(params)),
                jax.make_jaxpr(logistic._forward)(
                    params, X, X.mean(0), X.std(0)
                ),
            ]
        text = "\n".join(str(j) for j in jaxprs)
        dots = text.count("dot_general[")
        assert dots == text.count("precision=(Precision.HIGHEST, Precision.HIGHEST)")
        assert dots >= 4
