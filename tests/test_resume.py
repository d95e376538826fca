"""Crash-resume contract (docs/robustness.md): progress artifacts,
resume-aware recovery, partial results, and the fault-point wiring that
the chaos drills lean on.

The bit-identity claim is load-bearing: a resumed fit must produce the
SAME model as an uninterrupted one, so resume is a pure wall-clock
optimization with no accuracy asterisk. The tests here prove it at the
unit level (segment restore → identical params); the subprocess kill -9
drill in tests/test_chaos.py proves it end to end.
"""

import os
import shutil

import numpy as np
import pytest

from learningorchestra_tpu.core.ingest import ingest_csv, write_ingest_metadata
from learningorchestra_tpu.core.store import METADATA_ID, ROW_ID
from learningorchestra_tpu.ml.progress import ProgressSink, bind_sink, device_restore
from learningorchestra_tpu.ops.dtype import convert_field_types
from learningorchestra_tpu.sched.journal import JobJournal
from learningorchestra_tpu.telemetry import metrics as metrics_mod
from learningorchestra_tpu.testing import faults
from tests.test_frame import DOCUMENTED_PREPROCESSOR

NUMERIC_FIELDS = ("PassengerId", "Survived", "Pclass", "Age", "SibSp", "Parch", "Fare")

META = {"training_fp": "a" * 16, "test_fp": "b" * 16, "dtype_policy": "f32", "mesh": "m"}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture()
def titanic_store(store, titanic_csv):
    for name in ("titanic_train", "titanic_test"):
        write_ingest_metadata(store, name, titanic_csv)
        ingest_csv(store, name, titanic_csv)
        convert_field_types(store, name, {f: "number" for f in NUMERIC_FIELDS})
    return store


def _counter_value(name: str) -> float:
    registry = metrics_mod.global_registry()
    counter = registry.counter(name, "probe")
    return counter.value()


class TestProgressSink:
    def test_round_trip(self, tmp_path):
        sink = ProgressSink(str(tmp_path / "m.progress"), dict(META))
        arrays = [np.arange(6.0).reshape(2, 3), np.array([1, 2], np.int32)]
        sink.save("logistic", 2, arrays, {"iters": 25, "history": [0.5]})
        restored = sink.load("logistic")
        assert restored is not None
        segment, back, scalars = restored
        assert segment == 2
        assert scalars == {"iters": 25, "history": [0.5]}
        np.testing.assert_array_equal(back[0], arrays[0])
        np.testing.assert_array_equal(back[1], arrays[1])
        assert back[1].dtype == np.int32

    def test_every_grid_skips_off_grid_segments(self, tmp_path):
        fired = []
        sink = ProgressSink(
            str(tmp_path / "m.progress"),
            dict(META),
            every=2,
            on_segment=fired.append,
        )
        sink.save("logistic", 1, [np.zeros(2)], {})
        assert not os.path.exists(sink.path)
        assert fired == []
        sink.save("logistic", 2, [np.zeros(2)], {})
        assert os.path.exists(sink.path)
        assert fired == [2]

    def test_kind_mismatch_deletes(self, tmp_path):
        sink = ProgressSink(str(tmp_path / "m.progress"), dict(META))
        sink.save("logistic", 1, [np.zeros(2)], {})
        assert sink.load("gbt") is None
        assert not os.path.exists(sink.path)

    def test_stale_meta_deletes(self, tmp_path):
        path = str(tmp_path / "m.progress")
        ProgressSink(path, dict(META)).save("logistic", 1, [np.zeros(2)], {})
        stale = dict(META, training_fp="c" * 16)
        assert ProgressSink(path, stale).load("logistic") is None
        assert not os.path.exists(path)

    def test_corrupt_artifact_deletes(self, tmp_path):
        path = str(tmp_path / "m.progress")
        with open(path, "wb") as handle:
            handle.write(b"not a zip archive")
        assert ProgressSink(path, dict(META)).load("logistic") is None
        assert not os.path.exists(path)

    def test_discard_and_missing_file(self, tmp_path):
        sink = ProgressSink(str(tmp_path / "m.progress"), dict(META))
        assert sink.load("logistic") is None  # nothing saved yet
        sink.save("logistic", 1, [np.zeros(2)], {})
        sink.discard()
        assert not os.path.exists(sink.path)
        sink.discard()  # idempotent


class TestCollectionFingerprint:
    """The validation key must survive a process restart — collection
    revs reseed from a random base per boot, which is why the key uses
    content fingerprints instead (the restarted process is the one that
    needs a pre-crash artifact to validate)."""

    def test_stable_across_wal_reload(self, tmp_path):
        from learningorchestra_tpu.core.store import InMemoryStore
        from learningorchestra_tpu.ml.progress import collection_fingerprint

        data_dir = str(tmp_path / "lo_data")
        first = InMemoryStore(data_dir=data_dir)
        first.insert_many(
            "drill", [{"_id": i, "f1": i * 0.5} for i in range(1, 6)]
        )
        before = collection_fingerprint(first, "drill")

        second = InMemoryStore(data_dir=data_dir)  # same WAL, new boot
        assert second.collection_rev("drill") != first.collection_rev(
            "drill"
        ), "revs ARE boot-scoped; if this ever holds, revs would suffice"
        assert collection_fingerprint(second, "drill") == before

    def test_mutation_changes_fingerprint(self, store):
        from learningorchestra_tpu.ml.progress import collection_fingerprint

        store.insert_many(
            "drill", [{"_id": i, "f1": i * 0.5} for i in range(1, 6)]
        )
        before = collection_fingerprint(store, "drill")
        store.update_one("drill", {"_id": 3}, {"f1": -1.0})
        assert collection_fingerprint(store, "drill") != before

    def test_save_is_best_effort(self, tmp_path):
        # an unwritable progress dir costs resume granularity, not the fit
        sink = ProgressSink(
            str(tmp_path / "missing_dir" / "m.progress"), dict(META)
        )
        sink.save("logistic", 1, [np.zeros(2)], {})  # must not raise
        assert sink.load("logistic") is None


class TestDeviceRestore:
    def _template(self):
        import jax.numpy as jnp

        return (jnp.zeros((2, 3), jnp.float32), jnp.zeros((3,), jnp.float32))

    def test_restores_matching_arrays(self):
        template = self._template()
        hosts = [
            np.arange(6, dtype=np.float32).reshape(2, 3),
            np.arange(3, dtype=np.float32),
        ]
        restored = device_restore(template, hosts)
        assert restored is not None
        np.testing.assert_array_equal(np.asarray(restored[0]), hosts[0])
        np.testing.assert_array_equal(np.asarray(restored[1]), hosts[1])

    def test_leaf_count_mismatch(self):
        assert device_restore(self._template(), [np.zeros((2, 3))]) is None

    def test_shape_mismatch(self):
        hosts = [np.zeros((2, 4), np.float32), np.zeros((3,), np.float32)]
        assert device_restore(self._template(), hosts) is None

    def test_dtype_mismatch(self):
        hosts = [np.zeros((2, 3), np.float64), np.zeros((3,), np.float32)]
        assert device_restore(self._template(), hosts) is None


class TestLogisticResumeBitIdentity:
    def test_resumed_fit_matches_uninterrupted(self, tmp_path):
        """Kill-at-segment-2 simulation: copy the segment-2 artifact
        aside mid-run, restore it, refit — the resumed fit must skip
        two segments and land on bit-identical params."""
        import jax

        from learningorchestra_tpu.ml.logistic import LogisticRegression

        rng = np.random.default_rng(11)
        X = rng.random((64, 5)).astype(np.float64)
        y = (X[:, 0] > 0.5).astype(np.int64)
        # tol tiny-but-positive keeps the 25-iteration convergence-check
        # segmentation (max_iter=100 → up to 4 segments); the fit may
        # still plateau early once fully converged (zero deltas pass any
        # positive tol), so the assertions below count segments rather
        # than assume all four run
        tol = 1e-12
        control = LogisticRegression(max_iter=100, tol=tol).fit(X, y)

        path = str(tmp_path / "m.progress")
        aside = str(tmp_path / "segment2.progress")
        segments: list[int] = []

        def record(segment: int) -> None:
            segments.append(segment)
            if segment == 2:
                shutil.copyfile(path, aside)

        first = ProgressSink(path, dict(META), on_segment=record)
        with bind_sink(first):
            uninterrupted = LogisticRegression(max_iter=100, tol=tol).fit(X, y)
        assert os.path.exists(aside), "fit never reached segment 2"
        total_run = segments[-1]
        assert total_run >= 2

        # the "restarted process": same meta, the mid-fit artifact back
        # in place
        shutil.copyfile(aside, path)
        skipped_before = _counter_value("lo_build_segments_skipped_total")
        saved_before = _counter_value("lo_build_segments_saved_total")
        with bind_sink(ProgressSink(path, dict(META))):
            resumed = LogisticRegression(max_iter=100, tol=tol).fit(X, y)
        assert _counter_value("lo_build_segments_skipped_total") - skipped_before == 2
        # the resumed run re-runs EXACTLY the segments the control ran
        # past the restore point — stopping where the control stopped,
        # even when that is "immediately" (plateau checked at loop top)
        assert (
            _counter_value("lo_build_segments_saved_total") - saved_before
            == total_run - 2
        )

        for fitted in (uninterrupted, resumed):
            for got, want in zip(
                jax.tree.leaves(fitted.params), jax.tree.leaves(control.params)
            ):
                np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("rows", ["separable", "offset"])
    def test_resumed_fit_reseeds_its_margins_bit_identically(
        self, tmp_path, rows
    ):
        """The margins ride in the segment program and are seeded from
        W at each segment's entry, so a fit killed after segment 1 and
        resumed from the artifact (params and optimizer state only)
        lands on the uninterrupted fit's params to the bit — on rows
        whose line search backtracks (separable labels) and on a
        column far from zero that the device standardises."""
        import jax

        from learningorchestra_tpu.ml.logistic import LogisticRegression

        rng = np.random.default_rng(13)
        X = rng.normal(size=(200, 4))
        if rows == "separable":
            y = (X[:, 0] > 0).astype(np.int64)
        else:
            X[:, 2] += 1e4
            y = (X[:, 0] + rng.normal(size=200) > 0).astype(np.int64)
        make = lambda: LogisticRegression(max_iter=75, tol=0.0)  # noqa: E731

        path = str(tmp_path / "m.progress")
        aside = str(tmp_path / "segment1.progress")

        def record(segment: int) -> None:
            if segment == 1:
                shutil.copyfile(path, aside)

        with bind_sink(ProgressSink(path, dict(META), on_segment=record)):
            uninterrupted = make().fit(X, y)
        shutil.copyfile(aside, path)
        skipped_before = _counter_value("lo_build_segments_skipped_total")
        with bind_sink(ProgressSink(path, dict(META))):
            resumed = make().fit(X, y)
        assert (
            _counter_value("lo_build_segments_skipped_total") - skipped_before
            == 1
        )
        for got, want in zip(
            jax.tree.leaves(resumed.params),
            jax.tree.leaves(uninterrupted.params),
        ):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(resumed.mean), np.asarray(uninterrupted.mean)
        )

    def test_stale_artifact_restarts_clean(self, tmp_path):
        """A rev-mismatched artifact must be deleted and the fit rerun
        from scratch — never resumed into a silently-wrong model."""
        import jax

        from learningorchestra_tpu.ml.logistic import LogisticRegression

        rng = np.random.default_rng(12)
        X = rng.random((48, 4)).astype(np.float64)
        y = (X[:, 1] > 0.5).astype(np.int64)
        control = LogisticRegression(max_iter=50, tol=1e-12).fit(X, y)

        path = str(tmp_path / "m.progress")
        with bind_sink(ProgressSink(path, dict(META))):
            LogisticRegression(max_iter=50, tol=1e-12).fit(X, y)
        assert os.path.exists(path)

        stale = dict(META, training_fp="c" * 16)
        skipped_before = _counter_value("lo_build_segments_skipped_total")
        with bind_sink(ProgressSink(path, stale)):
            refit = LogisticRegression(max_iter=50, tol=1e-12).fit(X, y)
        assert _counter_value("lo_build_segments_skipped_total") == skipped_before
        for got, want in zip(
            jax.tree.leaves(refit.params), jax.tree.leaves(control.params)
        ):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestDonatedSegmentPrograms:
    """The chip runs the DONATED segment programs (selected by backend,
    ml/logistic.py, ml/trees.py); tier-1 runs the undonated ones. The
    CPU backend implements donation too, so force the chip's programs
    here: a progress sink reads (params, opt_state) / margins right
    after each donated call, and a read of a donated input would raise
    "Array has been deleted"."""

    def test_donated_fits_with_a_sink_match_the_undonated(
        self, tmp_path, monkeypatch
    ):
        import jax

        from learningorchestra_tpu.ml import logistic, trees

        rng = np.random.default_rng(5)
        X = rng.random((256, 6))
        y = (X[:, 0] + X[:, 1] > 1.0).astype(np.int64)
        makers = {
            "lr": lambda: logistic.LogisticRegression(max_iter=50, tol=1e-12),
            "gb": lambda: trees.GBTClassifier(rounds=4, max_depth=3),
        }
        control = {name: make().fit(X, y) for name, make in makers.items()}

        monkeypatch.setattr(
            logistic, "_fit_segment_runner", logistic._donated_fit_segment
        )
        monkeypatch.setattr(
            trees, "_gbt_rounds_runner", trees._donated_gbt_rounds
        )
        for name, make in makers.items():
            sink = ProgressSink(str(tmp_path / f"{name}.progress"), dict(META))
            with bind_sink(sink):
                donated = make().fit(X, y)
            for got, want in zip(
                jax.tree.leaves(vars(donated)),
                jax.tree.leaves(vars(control[name])),
            ):
                if isinstance(want, jax.Array):
                    np.testing.assert_array_equal(
                        np.asarray(got), np.asarray(want)
                    )


class TestJournalProgress:
    def test_progress_folds_without_touching_state(self, store):
        journal = JobJournal(store)
        journal.append("j1", "submitted", op="build_model", payload={"a": 1})
        journal.append("j1", "started")
        journal.append("j1", "progress", classificator="lr", status="finished")
        journal.append("j1", "progress", classificator="dt", kind="segment", segment=3)
        history = journal.replay()["j1"]
        assert history.started and not history.terminal
        assert len(history.progress) == 2
        assert history.progress[0]["classificator"] == "lr"
        assert history.progress[1]["segment"] == 3

    def test_terminal_after_finish(self, store):
        journal = JobJournal(store)
        journal.append("j1", "submitted", op="build_model", payload={})
        journal.append("j1", "started")
        journal.append("j1", "progress", classificator="lr", status="finished")
        journal.append("j1", "finished")
        assert journal.replay()["j1"].terminal

    def test_append_fault_loses_audit_line_not_job(self, store):
        # chaos point sched.journal.append: an injected error must be
        # swallowed exactly like a real store hiccup
        journal = JobJournal(store)
        faults.install("sched.journal.append", "error@1")
        journal.append("j1", "submitted", op="build_model", payload={})
        journal.append("j1", "started")
        history = journal.replay().get("j1")
        # the submitted line was lost; the started line synthesized a
        # history so recovery can still terminate it
        assert history is not None and history.started


class _FakeJobs:
    def __init__(self):
        self.submissions = []
        self.journal = None

    def submit(self, name, fn, *args, **kwargs):
        self.submissions.append((name, fn, args, kwargs))


class TestRecoveryResume:
    @pytest.fixture(autouse=True)
    def _registries(self):
        from learningorchestra_tpu.sched import recovery

        replay = dict(recovery._REPLAY_REGISTRY)
        resume = dict(recovery._RESUME_REGISTRY)
        yield
        recovery._REPLAY_REGISTRY.clear()
        recovery._REPLAY_REGISTRY.update(replay)
        recovery._RESUME_REGISTRY.clear()
        recovery._RESUME_REGISTRY.update(resume)

    def _orphan_journal(self, store, op="stub_op", collection="c1"):
        journal = JobJournal(store)
        journal.append(
            "j1", "submitted", op=op, payload={"x": 1}, collection=collection
        )
        journal.append("j1", "started")
        journal.append("j1", "progress", classificator="lr", status="finished")
        journal.append("j1", "progress", classificator="dt", kind="segment", segment=2)
        return journal

    def test_orphaned_resumable_job_requeues_with_progress(self, store):
        from learningorchestra_tpu.sched import recovery

        def handler(store, payload, progress):
            raise AssertionError("recovery must enqueue, not run inline")

        recovery.register_resumable("stub_op", handler)
        journal = self._orphan_journal(store)
        jobs = _FakeJobs()
        resumed_before = _counter_value("lo_sched_resumed_total")
        outcome = recovery.recover_jobs(store, jobs, journal)
        assert outcome == {"requeued": ["j1"], "orphaned": []}
        assert _counter_value("lo_sched_resumed_total") - resumed_before == 1
        (name, fn, args, kwargs) = jobs.submissions[0]
        assert name == "j1" and fn is handler
        assert args[1] == {"x": 1}
        progress = args[2]
        assert [e.get("classificator") for e in progress] == ["lr", "dt"]
        assert kwargs["replay"] == ("stub_op", {"x": 1})
        # still RUNNING as far as the journal knows: no terminal event
        assert not journal.replay()["j1"].terminal

    def test_resume_disabled_orphans_instead(self, store, monkeypatch):
        from learningorchestra_tpu.sched import recovery

        monkeypatch.setenv("LO_RESUME", "0")
        recovery.register_resumable(
            "stub_op", lambda store, payload, progress: None
        )
        store.insert_one("c1", {ROW_ID: METADATA_ID, "finished": False})
        journal = self._orphan_journal(store)
        jobs = _FakeJobs()
        outcome = recovery.recover_jobs(store, jobs, journal)
        assert outcome == {"requeued": [], "orphaned": ["j1"]}
        assert jobs.submissions == []
        history = journal.replay()["j1"]
        assert history.terminal and history.last_error == recovery.ORPHAN_ERROR
        metadata = store.find_one("c1", {ROW_ID: METADATA_ID})
        assert metadata["finished"] is True
        assert metadata["error"] == recovery.ORPHAN_ERROR

    def test_non_resumable_started_op_orphans(self, store):
        from learningorchestra_tpu.sched import recovery

        journal = self._orphan_journal(store, op="no_such_op")
        jobs = _FakeJobs()
        outcome = recovery.recover_jobs(store, jobs, journal)
        assert outcome == {"requeued": [], "orphaned": ["j1"]}

    def test_build_model_registered_both_ways(self):
        from learningorchestra_tpu.sched import recovery

        assert "build_model" in recovery._REPLAY_REGISTRY
        assert "build_model" in recovery._RESUME_REGISTRY


class _FakeHandle:
    def __init__(self):
        self.detail = {}
        self.events = []

    def annotate(self, **detail):
        self.detail.update(detail)

    def progress(self, **fields):
        self.events.append(fields)


@pytest.fixture()
def fake_handle(monkeypatch):
    handle = _FakeHandle()
    monkeypatch.setattr(
        "learningorchestra_tpu.core.jobs.current_job_handle", lambda: handle
    )
    return handle


def _build(store, classifiers, **kwargs):
    from learningorchestra_tpu.ml.builder import build_model

    return build_model(
        store,
        "titanic_train",
        "titanic_test",
        DOCUMENTED_PREPROCESSOR,
        classifiers,
        **kwargs,
    )


def _fail_member(monkeypatch, *names):
    from learningorchestra_tpu.ml import builder

    real = builder.train_one

    def failing(store, name, *args, **kwargs):
        if name in names:
            raise RuntimeError(f"{name} exploded")
        return real(store, name, *args, **kwargs)

    monkeypatch.setattr(builder, "train_one", failing)


class TestPartialResults:
    def test_one_failure_returns_survivors(
        self, titanic_store, monkeypatch, fake_handle
    ):
        _fail_member(monkeypatch, "nb")
        results = _build(titanic_store, ["lr", "nb"])
        assert [r["classificator"] for r in results] == ["lr"]
        assert fake_handle.detail["result"] == "finished_partial"
        statuses = fake_handle.detail["classifiers"]
        assert statuses["lr"] == {"status": "finished"}
        assert statuses["nb"]["status"] == "failed"
        assert "nb exploded" in statuses["nb"]["error"]
        # the journal trail the resumed run folds: lr durably finished,
        # nb permanently failed
        assert {"classificator": "lr", "status": "finished"} in fake_handle.events
        failed = [e for e in fake_handle.events if e.get("status") == "failed"]
        assert failed and failed[0]["classificator"] == "nb"

    def test_single_member_failure_reraises_verbatim(
        self, titanic_store, monkeypatch, fake_handle
    ):
        _fail_member(monkeypatch, "nb")
        with pytest.raises(RuntimeError, match="nb exploded"):
            _build(titanic_store, ["nb"])
        assert "result" not in fake_handle.detail

    def test_all_failed_multi_aggregates(
        self, titanic_store, monkeypatch, fake_handle
    ):
        _fail_member(monkeypatch, "lr", "nb")
        with pytest.raises(RuntimeError, match="all classifiers failed"):
            _build(titanic_store, ["lr", "nb"])

    def test_fault_injected_member_yields_partial(
        self, titanic_store, fake_handle
    ):
        # the compute-plane chaos point: one classifier's fit phase
        # errors, the build still FINISHES with the survivor's outputs
        faults.install(
            "builder.phase", "error@1", where={"phase": "fit", "classificator": "nb"}
        )
        results = _build(titanic_store, ["lr", "nb"])
        assert [r["classificator"] for r in results] == ["lr"]
        assert fake_handle.detail["result"] == "finished_partial"
        assert fake_handle.detail["classifiers"]["nb"]["status"] == "failed"


class TestResumeSkips:
    def test_finished_member_not_refit(
        self, titanic_store, monkeypatch, fake_handle
    ):
        results = _build(titanic_store, ["lr"])
        stored = titanic_store.find_one(
            "titanic_test_prediction_lr", {ROW_ID: 0}
        )
        assert stored is not None
        fake_handle.events.clear()

        from learningorchestra_tpu.ml import builder

        def must_not_run(*args, **kwargs):
            raise AssertionError("finished member must not refit")

        monkeypatch.setattr(builder, "train_one", must_not_run)
        resumed = _build(
            titanic_store,
            ["lr"],
            resume=[{"classificator": "lr", "status": "finished"}],
        )
        assert resumed == [stored]
        assert fake_handle.events == []  # no re-journaled completion
        assert results[0]["accuracy"] == stored["accuracy"]

    def test_finished_member_with_dropped_outputs_rebuilds(
        self, titanic_store, fake_handle
    ):
        # journaled finished but the collection is gone: rebuild, don't
        # return nothing
        resumed = _build(
            titanic_store,
            ["lr"],
            resume=[{"classificator": "lr", "status": "finished"}],
        )
        assert resumed[0]["classificator"] == "lr"
        assert {"classificator": "lr", "status": "finished"} in fake_handle.events

    def test_failed_member_stays_failed_without_rerun(
        self, titanic_store, monkeypatch, fake_handle
    ):
        from learningorchestra_tpu.ml import builder

        real = builder.train_one

        def guarded(store, name, *args, **kwargs):
            assert name != "nb", "failed member must not re-run"
            return real(store, name, *args, **kwargs)

        monkeypatch.setattr(builder, "train_one", guarded)
        results = _build(
            titanic_store,
            ["lr", "nb"],
            resume=[
                {
                    "classificator": "nb",
                    "status": "failed",
                    "error": "boom before restart",
                }
            ],
        )
        assert [r["classificator"] for r in results] == ["lr"]
        statuses = fake_handle.detail["classifiers"]
        assert statuses["nb"] == {
            "status": "failed",
            "error": "boom before restart",
        }
        # already journaled by the pre-crash run: no duplicate event
        assert not any(
            e.get("status") == "failed" for e in fake_handle.events
        )

    def test_later_events_win_in_fold(self):
        from learningorchestra_tpu.ml.builder import _fold_resume

        done = _fold_resume(
            [
                {"classificator": "lr", "status": "failed", "error": "x"},
                {"classificator": "dt", "kind": "segment", "segment": 2},
                {"classificator": "lr", "status": "finished"},
            ]
        )
        assert done == {"lr": {"status": "finished", "error": None}}


class TestResumeKnobs:
    def test_defaults(self, monkeypatch):
        from learningorchestra_tpu.sched import config

        monkeypatch.delenv("LO_RESUME", raising=False)
        monkeypatch.delenv("LO_RESUME_EVERY_SEGMENTS", raising=False)
        assert config.resume_enabled() is True
        assert config.resume_every_segments() == 1

    def test_disable(self, monkeypatch):
        from learningorchestra_tpu.sched import config

        monkeypatch.setenv("LO_RESUME", "0")
        assert config.resume_enabled() is False

    @pytest.mark.parametrize("value", ["yes", "2", "true"])
    def test_enabled_rejects_non_binary(self, monkeypatch, value):
        from learningorchestra_tpu.sched import config

        monkeypatch.setenv("LO_RESUME", value)
        with pytest.raises(ValueError):
            config.resume_enabled()

    @pytest.mark.parametrize("value", ["0", "1.5", "-2", "abc"])
    def test_every_segments_rejects(self, monkeypatch, value):
        from learningorchestra_tpu.sched import config

        monkeypatch.setenv("LO_RESUME_EVERY_SEGMENTS", value)
        with pytest.raises(ValueError):
            config.resume_every_segments()

    def test_every_segments_accepts_integral(self, monkeypatch):
        from learningorchestra_tpu.sched import config

        monkeypatch.setenv("LO_RESUME_EVERY_SEGMENTS", "3")
        assert config.resume_every_segments() == 3
