"""End-to-end model builder: Titanic-like data through the documented
preprocessor into all five classifiers."""

import numpy as np
import pytest

from learningorchestra_tpu.core.ingest import ingest_csv, write_ingest_metadata
from learningorchestra_tpu.core.store import ROW_ID
from learningorchestra_tpu.ml.builder import build_model
from learningorchestra_tpu.ops.dtype import convert_field_types
from tests.test_frame import DOCUMENTED_PREPROCESSOR

NUMERIC_FIELDS = ("PassengerId", "Survived", "Pclass", "Age", "SibSp", "Parch", "Fare")


@pytest.fixture()
def titanic_store(store, titanic_csv):
    for name in ("titanic_train", "titanic_test"):
        write_ingest_metadata(store, name, titanic_csv)
        ingest_csv(store, name, titanic_csv)
        convert_field_types(store, name, {f: "number" for f in NUMERIC_FIELDS})
    return store


class TestBuildModel:
    def test_lr_and_nb(self, titanic_store):
        results = build_model(
            titanic_store,
            "titanic_train",
            "titanic_test",
            DOCUMENTED_PREPROCESSOR,
            ["lr", "nb"],
        )
        assert {r["classificator"] for r in results} == {"lr", "nb"}
        for result in results:
            name = result["filename"]
            assert name.startswith("titanic_test_prediction_")
            meta = titanic_store.find_one(name, {ROW_ID: 0})
            assert meta["fit_time"] > 0
            assert "F1" in meta and isinstance(meta["F1"], str)
            assert "accuracy" in meta and isinstance(meta["accuracy"], str)
            rows = [
                d
                for d in titanic_store.find(name)
                if d[ROW_ID] != 0
            ]
            assert len(rows) == 8
            assert "prediction" in rows[0]
            assert isinstance(rows[0]["probability"], list)
            assert "features" not in rows[0]

    def test_two_warm_builds_complete_concurrently(self, titanic_store):
        """Regression for the PR 8 KNOWN LATENT: on the 8-virtual-device
        CPU backend, two warm builds running their collective evals
        concurrently used to deadlock XLA's CPU rendezvous (each
        program's participants holding part of the host thread pool,
        waiting on peers the other program occupies). The
        _collective_dispatch_guard in ml/builder.py now serializes
        those dispatches on single-process CPU, so two concurrent
        builds must COMPLETE — and agree with each other."""
        import threading

        # warm build: compiles every program so the concurrent pair
        # below executes already-compiled collectives (the deadlock's
        # trigger condition)
        build_model(
            titanic_store,
            "titanic_train",
            "titanic_test",
            DOCUMENTED_PREPROCESSOR,
            ["lr", "nb", "dt"],
        )
        results: dict = {}

        def run(slot: str) -> None:
            try:
                results[slot] = build_model(
                    titanic_store,
                    "titanic_train",
                    "titanic_test",
                    DOCUMENTED_PREPROCESSOR,
                    ["lr", "nb", "dt"],
                    # the second build writes to a distinct prediction
                    # namespace only through timing; writing outputs
                    # from both is fine (same collections, drop+insert)
                )
            except BaseException as error:  # noqa: BLE001 — asserted below
                results[slot] = error

        threads = [
            threading.Thread(target=run, args=(slot,), daemon=True)
            for slot in ("a", "b")
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            # generous bound: a deadlock parks forever, a healthy pair
            # of warm 3-classifier builds takes seconds
            thread.join(timeout=300)
        assert not any(t.is_alive() for t in threads), (
            "concurrent warm builds did not complete — the CPU "
            "rendezvous guard regressed"
        )
        for slot in ("a", "b"):
            assert not isinstance(results[slot], BaseException), results[slot]
            assert {r["classificator"] for r in results[slot]} == {
                "lr",
                "nb",
                "dt",
            }

    def test_invalid_classifier_raises(self, titanic_store):
        with pytest.raises(KeyError):
            build_model(
                titanic_store,
                "titanic_train",
                "titanic_test",
                DOCUMENTED_PREPROCESSOR,
                ["svm"],
            )

    def test_no_evaluation_split(self, titanic_store):
        code = DOCUMENTED_PREPROCESSOR.replace(
            "(features_training, features_evaluation) =\\\n"
            "    features_training.randomSplit([0.8, 0.2], seed=33)",
            "features_evaluation = None",
        )
        results = build_model(
            titanic_store, "titanic_train", "titanic_test", code, ["nb"]
        )
        assert "F1" not in results[0]


class TestBuildTraceSpans:
    """What a library-level build leaves in an active trace beside the
    phases: the resume fingerprint's own span, and no ``devices``."""

    def _build(self, store, classifiers, **kwargs):
        from learningorchestra_tpu.telemetry import tracing

        trace = tracing.Trace(name="build")
        with tracing.activate(trace), tracing.span("job:build:test"):
            build_model(
                store,
                "titanic_train",
                "titanic_test",
                DOCUMENTED_PREPROCESSOR,
                classifiers,
                **kwargs,
            )
        (root,) = trace.as_dict()["spans"]
        return root

    def test_fingerprint_span_when_progress_is_journaled(
        self, titanic_store, tmp_path
    ):
        root = self._build(
            titanic_store, ["nb"], models_dir=str(tmp_path / "models")
        )
        names = [child["name"] for child in root["children"]]
        assert names.count("resume:fingerprint") == 1
        assert names.index("preprocess") < names.index(
            "resume:fingerprint"
        ) < names.index("train:nb")
        (span,) = [
            c for c in root["children"] if c["name"] == "resume:fingerprint"
        ]
        assert span["meta"]["rows"] == 16  # both collections' rows

    @pytest.mark.parametrize(
        "resume, models_dir", [("0", "models"), ("1", "")]
    )
    def test_no_fingerprint_span_when_nothing_is_hashed(
        self, titanic_store, tmp_path, monkeypatch, resume, models_dir
    ):
        monkeypatch.setenv("LO_RESUME", resume)
        root = self._build(
            titanic_store,
            ["nb"],
            models_dir=str(tmp_path / models_dir) if models_dir else "",
        )
        names = [child["name"] for child in root["children"]]
        assert "resume:fingerprint" not in names
        assert "devices" not in names
        assert {"load_data", "preprocess", "train:nb"} <= set(names)

    def test_nb_fit_has_a_transfer_and_a_wait_and_no_host_pass(
        self, titanic_store
    ):
        root = self._build(titanic_store, ["nb"])
        (train,) = [c for c in root["children"] if c["name"] == "train:nb"]
        (fit,) = [c for c in train["children"] if c["name"] == "phase:fit"]
        names = [
            c["name"] for c in fit["children"]
            if not c["name"].startswith("compile:")
        ]
        assert names == ["h2d:train", "fit:enqueue", "fit:device_wait"]


class TestFusedEvaluatePredict:
    """ml/base.evaluate_predict: metrics + predictions in ONE device→host
    transfer, sharing the forward pass when eval and test frames alias
    (the VERDICT-r4 evaluate/predict tail collapse)."""

    def _fit_nb(self, rows=256):
        import numpy as np

        from learningorchestra_tpu.ml.naive_bayes import NaiveBayes

        rng = np.random.default_rng(3)
        X = rng.random((rows, 6)).astype(np.float32)
        y = (X[:, 0] > 0.5).astype(np.int32)
        return NaiveBayes().fit(X, y), X, y

    def test_matches_separate_calls(self):
        import numpy as np

        from learningorchestra_tpu.ml.base import shard_labels, shard_matrix

        model, X, y = self._fit_nb()
        Xd = shard_matrix(X)
        yd = shard_labels(y)
        accuracy, f1, labels, probs = model.evaluate_predict(Xd, yd, Xd)
        sep_accuracy, sep_f1 = model.evaluate(Xd, yd)
        sep_labels, sep_probs = model.predict_both(Xd)
        assert accuracy == sep_accuracy and f1 == sep_f1
        np.testing.assert_array_equal(labels, sep_labels)
        np.testing.assert_allclose(probs, sep_probs)
        assert len(labels) == len(X)  # padding cropped

    def test_distinct_test_frame(self):
        import numpy as np

        from learningorchestra_tpu.ml.base import shard_labels, shard_matrix

        model, X, y = self._fit_nb()
        X_test = X[:100] * 0.5  # different content AND row count
        Xd_eval = shard_matrix(X)
        Xd_test = shard_matrix(X_test)
        yd = shard_labels(y)
        accuracy, _, labels, probs = model.evaluate_predict(
            Xd_eval, yd, Xd_test
        )
        assert len(labels) == len(probs) == 100
        sep_labels, _ = model.predict_both(Xd_test)
        np.testing.assert_array_equal(labels, sep_labels)
        assert accuracy == model.evaluate(Xd_eval, yd)[0]

    def test_alias_if_equal_aliases_only_equal_frames(self):
        import numpy as np

        from learningorchestra_tpu.frame.dataframe import DataFrame
        from learningorchestra_tpu.ml.builder import _alias_if_equal

        X = np.arange(12, dtype=np.float64).reshape(4, 3)
        base = {
            "features": X,
            "label": np.array([0.0, 1.0, 0.0, 1.0]),
        }
        testing = DataFrame(dict(base))
        equal = DataFrame({"features": X.copy(), "label": base["label"].copy()})
        different = DataFrame(
            {"features": X + 1, "label": base["label"].copy()}
        )
        assert _alias_if_equal(equal, testing) is testing
        assert _alias_if_equal(different, testing) is different
        assert _alias_if_equal(None, testing) is None


def _traced_spans(work):
    """``[(span, path)]`` of every span ``work`` leaves in an active
    trace."""
    from learningorchestra_tpu.telemetry import tracing

    def walk(span, path):
        here = path + (span["name"],)
        yield span, here
        for child in span["children"]:
            yield from walk(child, here)

    trace = tracing.Trace(name="build")
    with tracing.activate(trace):
        work()
    return [pair for root in trace.as_dict()["spans"] for pair in walk(root, ())]


def _threshold_spans(work):
    """``[(span, path)]`` of the ``fit:thresholds`` spans ``work``
    leaves in an active trace."""
    return [
        pair for pair in _traced_spans(work) if pair[0]["name"] == "fit:thresholds"
    ]


FIVE = ["lr", "dt", "rf", "gb", "nb"]
TREES = ["dt", "rf", "gb"]


class TestOneTrainingCopyABuild:
    """A build's training matrix and labels cross to the device once,
    whatever the number of fits: the first fit to ask makes the copy
    under ``h2d:train``, the others wait for it under ``fit:input``."""

    @pytest.mark.parametrize("classifiers", [FIVE, TREES])
    def test_one_copy_serves_every_fit(self, titanic_store, classifiers):
        from learningorchestra_tpu.parallel.mesh import data_size, default_mesh
        from learningorchestra_tpu.parallel.sharding import padded_row_count
        from learningorchestra_tpu.telemetry import profile

        def build():
            build_model(
                titanic_store,
                "titanic_train",
                "titanic_test",
                DOCUMENTED_PREPROCESSOR,
                classifiers,
            )

        build()  # from here on the test split is in the device cache
        before = profile.flow_totals()["h2d_bytes"]
        spans = _traced_spans(build)
        grown = profile.flow_totals()["h2d_bytes"] - before
        (copy,) = [span for span, _ in spans if span["name"] == "h2d:train"]
        assert copy["meta"]["copies"] == 1
        # the one copy is all the build moved, to the byte: the padded
        # float32 matrix, int32 labels and a mask with each
        assert copy["meta"]["h2d_bytes"] == grown
        features = {
            span["meta"]["features"]
            for span, _ in spans
            if span["name"] == "fit:thresholds"
        }
        padded = padded_row_count(copy["meta"]["rows"], data_size(default_mesh()))
        assert grown == padded * (features.pop() * 4 + 1 + 4 + 1)
        for name in classifiers:
            (fit,) = [
                span for span, path in spans
                if span["name"] == "phase:fit" and f"train:{name}" in path
            ]
            children = [child["name"] for child in fit["children"]]
            assert children.count("h2d:train") + children.count("fit:input") == 1


class TestSharedTrainingInput:
    """The fits of a build work from one :class:`TrainingInput`: the
    same models as from the host arrays, and the shared arrays left as
    they were."""

    @pytest.fixture()
    def data(self, rng):
        X = rng.random((300, 5))  # non-negative, for naive Bayes
        return X, (X[:, 0] + X[:, 1] > 1).astype(np.int32)

    @pytest.mark.parametrize("name", FIVE)
    def test_a_fit_from_the_shared_input_is_the_fit_from_numpy(self, data, name):
        from learningorchestra_tpu.ml.base import TrainingInput, make_classifier

        X, y = data
        shared = make_classifier(name).fit(TrainingInput(X, y), y)
        alone = make_classifier(name).fit(X, y)
        state, want = shared.device_state(), alone.device_state()
        assert len(state) == len(want) > 0
        for array, expected in zip(state, want):
            np.testing.assert_array_equal(np.asarray(array), np.asarray(expected))
        np.testing.assert_array_equal(shared.predict_proba(X), alone.predict_proba(X))

    def test_the_shared_copy_is_left_as_it_was(self, data):
        from learningorchestra_tpu.ml.base import TrainingInput, make_classifier

        X, y = data
        shared = TrainingInput(X, y)
        matrix, labels = shared.device()
        arrays = (matrix.data, matrix.mask, labels.data)
        before = [np.asarray(array) for array in arrays]
        for name in FIVE:
            make_classifier(name).fit(shared, y)
        assert shared.device() == (matrix, labels)  # no second copy
        for array, was in zip(arrays, before):
            assert not array.is_deleted()
            np.testing.assert_array_equal(np.asarray(array), was)

    def test_many_fits_asking_at_once_get_one_copy_and_one_pass(
        self, data, monkeypatch
    ):
        import sys
        import threading

        from learningorchestra_tpu.ml import base, binning

        X, y = data
        shared = base.TrainingInput(X, y)
        made = {"copies": 0, "passes": 0}

        def counted(name, real):
            def run(*args, **kwargs):
                made[name] += 1
                return real(*args, **kwargs)

            return run

        monkeypatch.setattr(base, "shard_matrix", counted("copies", base.shard_matrix))
        monkeypatch.setattr(
            binning, "device_thresholds", counted("passes", binning.device_thresholds)
        )
        got = []

        def ask():
            matrix, labels = shared.device()
            got.append((matrix, labels) + shared.thresholds(matrix, 32))

        threads = [threading.Thread(target=ask) for _ in range(32)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            _run_side_by_side(threads)
        finally:
            sys.setswitchinterval(interval)
        assert made == {"copies": 1, "passes": 1}
        assert len(got) == 32
        assert sum(passes for *_, passes in got) == 1
        for part in range(3):  # the matrix, the labels, the thresholds
            assert len({id(answer[part]) for answer in got}) == 1


class TestThresholdsOnceABuild:
    """The bin thresholds of a build are made once: the first tree fit
    to have its matrix on the device runs the pass, the other two take
    its result (``passes`` on each fit's ``fit:thresholds`` span)."""

    def _build(self, store, train="titanic_train"):
        return build_model(
            store, train, "titanic_test", DOCUMENTED_PREPROCESSOR, TREES
        )

    def test_three_spans_one_pass(self, titanic_store):
        found = _threshold_spans(lambda: self._build(titanic_store))
        assert sorted(path[-3:] for _, path in found) == [
            (f"train:{name}", "phase:fit", "fit:thresholds")
            for name in sorted(TREES)
        ]
        assert sum(span["meta"]["passes"] for span, _ in found) == 1

    @pytest.mark.parametrize("second", ["titanic_train", "titanic_test"])
    def test_every_build_makes_its_own(self, titanic_store, second):
        def two_builds():
            self._build(titanic_store)
            self._build(titanic_store, train=second)

        found = _threshold_spans(two_builds)
        assert len(found) == 6
        assert sum(span["meta"]["passes"] for span, _ in found) == 2

    @pytest.mark.parametrize("name", TREES)
    def test_a_lone_fit_makes_its_own(self, rng, name):
        from learningorchestra_tpu.ml.base import make_classifier

        X = rng.normal(size=(200, 4))
        y = (X[:, 0] > 0).astype(np.int32)
        (first,), (again,) = (
            _threshold_spans(lambda: make_classifier(name).fit(X, y))
            for _ in range(2)
        )
        # a fit handed a host array makes its own input: nothing of the
        # first fit's pass outlives it, even for the same array object
        assert first[0]["meta"]["passes"] == again[0]["meta"]["passes"] == 1

    def test_a_failed_pass_fails_every_waiting_fit_and_is_forgotten(
        self, rng, monkeypatch
    ):
        import threading

        from learningorchestra_tpu.ml import base, binning
        from learningorchestra_tpu.ml.base import TrainingInput, make_classifier

        X = rng.normal(size=(200, 4))
        y = (X[:, 0] > 0).astype(np.int32)
        shared = TrainingInput(X, y)
        shared.device()  # the copy is made: only the pass is waited for
        waiting = threading.Semaphore(0)

        class Watched(base.Future):
            def result(self, timeout=None):
                waiting.release()
                return super().result(timeout)

        passes = []

        def failing_pass(*args):
            passes.append(args)
            # not before the other two fits wait for this one
            assert waiting.acquire(timeout=60) and waiting.acquire(timeout=60)
            raise RuntimeError("the pass broke")

        errors = {}

        def fit(name):
            try:
                make_classifier(name).fit(shared, y)
            except RuntimeError as error:
                errors[name] = error

        with monkeypatch.context() as patch:
            patch.setattr(base, "Future", Watched)
            patch.setattr(binning, "device_thresholds", failing_pass)
            threads = [
                threading.Thread(target=fit, args=(name,)) for name in TREES
            ]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=120)
            assert not any(thread.is_alive() for thread in threads)
        assert len(passes) == 1
        assert sorted(errors) == sorted(TREES)
        assert len({id(error) for error in errors.values()}) == 1
        assert str(errors["dt"]) == "the pass broke"
        # the next fit on the same input starts afresh
        ((span, _),) = _threshold_spans(
            lambda: make_classifier("dt").fit(shared, y)
        )
        assert span["meta"]["passes"] == 1

    def test_concurrent_builds_keep_their_own_thresholds(self, rng):
        """Two builds' inputs on different data, their six tree fits
        side by side: each input runs its own pass, and each fit bins by
        its own data's thresholds."""
        import threading

        from learningorchestra_tpu.ml.base import TrainingInput, make_classifier
        from learningorchestra_tpu.telemetry import tracing

        inputs = []
        for scale in (1.0, 10.0):
            X = rng.normal(size=(300, 4)) * scale
            inputs.append(TrainingInput(X, (X[:, 0] > 0).astype(np.int32)))
        fitted = {}

        def side_by_side():
            context = tracing.capture()

            def fit(index, name):
                with tracing.attach(context):
                    fitted[index, name] = make_classifier(name).fit(inputs[index])

            _run_side_by_side([
                threading.Thread(target=fit, args=(index, name))
                for index in range(2)
                for name in TREES
            ])

        found = _threshold_spans(side_by_side)
        assert len(found) == 6 and len(fitted) == 6
        assert sum(span["meta"]["passes"] for span, _ in found) == 2
        for index, data in enumerate(inputs):
            for name in TREES:
                alone = make_classifier(name).fit(data.X, data.y)
                np.testing.assert_array_equal(
                    np.asarray(fitted[index, name].thresholds_heap),
                    np.asarray(alone.thresholds_heap),
                )


def _run_side_by_side(threads) -> None:
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=300)
    assert not any(thread.is_alive() for thread in threads)
