"""Scheduler subsystem: admission control, priorities, retries,
deadlines, cancellation, journal recovery — and the end-to-end
guarantees the services inherit (device-class serialization, 429
backpressure, crash recovery leaving no job with ``finished: false``).
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from learningorchestra_tpu.core.jobs import (
    CANCELLED,
    FAILED,
    FINISHED,
    JobManager,
)
from learningorchestra_tpu.core.store import (
    METADATA_ID,
    ROW_ID,
    InMemoryStore,
)
from learningorchestra_tpu.sched import (
    DEVICE_CLASS,
    HOST_CLASS,
    JobJournal,
    QueueFullError,
    Scheduler,
    TransientJobError,
    backoff_delay,
    check_cancelled,
    recover_jobs,
)
from learningorchestra_tpu.sched import config as sched_config
from learningorchestra_tpu.sched.journal import JOURNAL_COLLECTION
from learningorchestra_tpu.sched.policy import is_transient


def body(response):
    return json.loads(response.get_data())


def make_manager(**scheduler_kwargs) -> JobManager:
    return JobManager(scheduler=Scheduler(**scheduler_kwargs))


# --------------------------------------------------------------------
# Admission control / backpressure
# --------------------------------------------------------------------


class TestAdmissionControl:
    def test_flood_past_queue_cap_is_deterministic_429(self):
        manager = make_manager(host_width=1, queue_cap=2)
        gate = threading.Event()
        manager.submit("hold", gate.wait)
        # give the single worker time to occupy itself with "hold"
        deadline = time.time() + 5
        while manager.get("hold").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        manager.submit("q1", lambda: None)
        manager.submit("q2", lambda: None)
        # cap=2 and 2 queued: every further submit MUST refuse, with a
        # positive Retry-After — deterministically, not racily
        for attempt in range(5):
            with pytest.raises(QueueFullError) as info:
                manager.submit(f"overflow{attempt}", lambda: None)
            assert info.value.retry_after_s >= 1
            assert info.value.job_class == HOST_CLASS
        # rejected submissions left no tracked record behind
        names = {job["name"] for job in manager.all_jobs()}
        assert names == {"hold", "q1", "q2"}
        gate.set()
        assert manager.wait("q2", timeout=10).state == FINISHED

    def test_rejected_name_is_resubmittable(self):
        manager = make_manager(host_width=1, queue_cap=1)
        gate = threading.Event()
        manager.submit("hold", gate.wait)
        deadline = time.time() + 5
        while manager.get("hold").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        manager.submit("fill", lambda: None)
        with pytest.raises(QueueFullError):
            manager.submit("again", lambda: None)
        gate.set()
        manager.wait("fill", timeout=10)
        # the 429'd name was fully unregistered: resubmit works
        manager.submit("again", lambda: None)
        assert manager.wait("again", timeout=10).state == FINISHED

    def test_rest_flood_429_with_retry_after(self, tmp_path):
        from learningorchestra_tpu.services import database_api

        store = InMemoryStore()
        jobs = make_manager(host_width=1, queue_cap=1)
        client = database_api.create_app(store, jobs).test_client()
        gate = threading.Event()
        jobs.submit("hold", gate.wait)
        deadline = time.time() + 5
        while jobs.get("hold").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        csv = tmp_path / "flood.csv"
        csv.write_text("a\n1\n")
        first = client.post(
            "/files", json={"filename": "flood0", "url": str(csv)}
        )
        assert first.status_code == 201
        rejected = client.post(
            "/files", json={"filename": "flood1", "url": str(csv)}
        )
        assert rejected.status_code == 429
        assert int(rejected.headers["Retry-After"]) >= 1
        assert body(rejected)["result"] == "queue_full"
        # the name claim was released with the rejection: after the
        # queue drains, the same request succeeds
        gate.set()
        jobs.wait("ingest:flood0", timeout=30)
        retried = client.post(
            "/files", json={"filename": "flood1", "url": str(csv)}
        )
        assert retried.status_code == 201
        jobs.wait("ingest:flood1", timeout=30)

    def test_priority_orders_queue(self):
        manager = make_manager(host_width=1, queue_cap=16)
        gate = threading.Event()
        order: list[str] = []
        manager.submit("hold", gate.wait)
        deadline = time.time() + 5
        while manager.get("hold").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        manager.submit("low", order.append, "low", priority=0)
        manager.submit("high", order.append, "high", priority=10)
        manager.submit("mid", order.append, "mid", priority=5)
        gate.set()
        for name in ("low", "high", "mid"):
            manager.wait(name, timeout=10)
        assert order == ["high", "mid", "low"]


def _queue_span(manager, name):
    """The job's ``sched:queue`` span and its ``job:*`` span, as
    ``GET /jobs/<name>/trace`` serves them."""
    (job,) = manager.get(name).trace_dict()["trace"]["spans"]
    assert job["name"] == f"job:{name}"
    (queue,) = [c for c in job["children"] if c["name"] == "sched:queue"]
    assert job["children"][0] is queue
    return queue, job


class TestWhatAQueuedJobWaitedBehind:
    """``sched:queue``: the wait from admission to start, and the runs
    of the class's workers that it was spent behind."""

    def test_a_job_admitted_behind_a_running_one_waited_its_remaining_run(
        self,
    ):
        manager = make_manager(device_width=1, queue_cap=8)
        manager.submit("slow:a", time.sleep, 0.3, job_class=DEVICE_CLASS)
        deadline = time.time() + 5
        while manager.get("slow:a").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        admitted = time.monotonic()
        manager.submit("build:b", lambda: None, job_class=DEVICE_CLASS)
        manager.wait("build:b", timeout=10)
        remaining = manager.get("slow:a").ended_at - (
            time.time() - (time.monotonic() - admitted)
        )
        queue, job = _queue_span(manager, "build:b")
        meta = queue["meta"]
        assert meta["behind_s"] == pytest.approx(remaining, abs=0.1)
        assert 0.1 < meta["behind_s"] <= job["meta"]["queue_wait_s"] + 1e-6
        assert meta["behind"] == 1
        assert meta["behind_kinds"] == ["slow"]
        assert queue["duration_s"] == pytest.approx(
            job["meta"]["queue_wait_s"], abs=1e-5
        )
        # it ends where the job starts, on the wall clock
        assert queue["start_ts"] + queue["duration_s"] == pytest.approx(
            job["start_ts"], abs=0.05
        )

    def test_on_an_idle_worker_it_waited_behind_nothing(self):
        manager = make_manager(device_width=1, queue_cap=8)
        manager.submit("first", lambda: None, job_class=DEVICE_CLASS)
        manager.wait("first", timeout=10)
        manager.submit("second", lambda: None, job_class=DEVICE_CLASS)
        manager.wait("second", timeout=10)
        for name in ("first", "second"):
            queue, job = _queue_span(manager, name)
            assert queue["meta"]["behind_s"] == 0
            assert queue["meta"]["behind"] == 0
            assert queue["meta"]["behind_kinds"] == []
            assert queue["start_ts"] + queue["duration_s"] == pytest.approx(
                job["start_ts"], abs=0.05
            )

    def test_behind_is_the_union_of_the_runs_and_names_each_kind_once(
        self,
    ):
        # two workers run three tasks, two of them at once: the wait
        # is spent behind their union, never more than the wait
        manager = make_manager(host_width=2, queue_cap=8)
        gate = threading.Event()
        manager.submit("warmup:a", gate.wait)
        manager.submit("warmup:b", gate.wait)
        deadline = time.time() + 5
        while manager.get("warmup:b").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        manager.submit("late", lambda: None)
        time.sleep(0.2)
        gate.set()
        manager.wait("late", timeout=10)
        queue, job = _queue_span(manager, "late")
        meta = queue["meta"]
        assert meta["behind"] == 2
        assert meta["behind_kinds"] == ["warmup"]
        assert 0.15 < meta["behind_s"] <= job["meta"]["queue_wait_s"] + 1e-6

    def test_the_log_of_runs_is_bounded(self):
        from learningorchestra_tpu.sched import scheduler as sched

        cls = sched._ClassQueue("host", 1, 8)
        for index in range(sched._RAN_LOG + 10):
            cls.ran.append([f"job{index}:x", float(index), index + 0.5])
        assert len(cls.ran) == sched._RAN_LOG
        seconds, runs, kinds = cls.behind_locked(
            sched._RAN_LOG + 8.0, sched._RAN_LOG + 10.0
        )
        assert seconds == pytest.approx(1.0)
        assert runs == 2
        assert kinds == (f"job{sched._RAN_LOG + 8}", f"job{sched._RAN_LOG + 9}")


# --------------------------------------------------------------------
# Retry policy
# --------------------------------------------------------------------


class TestRetryPolicy:
    def test_backoff_jitter_sequence_is_golden(self):
        # deterministic seeded jitter: the exact sequence is part of
        # the contract (journal replay re-derives the same delays)
        observed = [
            backoff_delay("build:x", n, base_s=0.5, cap_s=60.0, seed=0)
            for n in (1, 2, 3, 4, 5)
        ]
        assert observed == pytest.approx(
            [
                0.4633463628,
                1.094119149,
                2.4990444475,
                3.7282882016,
                6.9849966953,
            ]
        )
        # the cap bounds the exponential term before jitter
        capped = [
            backoff_delay("build:x", n, base_s=0.5, cap_s=2.0, seed=7)
            for n in (1, 2, 3)
        ]
        assert capped == pytest.approx(
            [0.4438675434, 0.9813357367, 1.7817060331]
        )
        # distinct jobs decorrelate; same job+attempt reproduces
        assert backoff_delay("a", 1, 0.5, 60.0, 0) != backoff_delay(
            "b", 1, 0.5, 60.0, 0
        )
        assert backoff_delay("a", 1, 0.5, 60.0, 0) == backoff_delay(
            "a", 1, 0.5, 60.0, 0
        )

    def test_transient_classification(self):
        assert is_transient(TransientJobError("hiccup"))
        assert not is_transient(ValueError("bad input"))

        class SpmdTimeoutError(RuntimeError):  # name-matched, no jax
            pass

        assert is_transient(SpmdTimeoutError("watchdog"))

    def test_transient_failure_retries_then_finishes(self, monkeypatch):
        monkeypatch.setenv("LO_SCHED_BACKOFF_S", "0.01")
        manager = make_manager()
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) < 3:
                raise TransientJobError("store failing over")

        manager.submit("flaky", flaky)
        record = manager.wait("flaky", timeout=30)
        assert record.state == FINISHED
        assert record.attempts == 3
        assert len(attempts) == 3

    def test_budget_exhausted_is_terminal_and_flips_finished(
        self, monkeypatch
    ):
        monkeypatch.setenv("LO_SCHED_BACKOFF_S", "0.01")
        monkeypatch.setenv("LO_SCHED_RETRIES", "2")
        store = InMemoryStore()
        store.insert_one(
            "ds", {ROW_ID: METADATA_ID, "filename": "ds", "finished": False}
        )
        manager = make_manager()

        def always_failing():
            raise TransientJobError("never recovers")

        manager.submit(
            "doomed", always_failing, store=store, collection="ds"
        )
        record = manager.wait("doomed", timeout=30)
        assert record.state == FAILED
        assert record.attempts == 2
        metadata = store.find_one("ds", {ROW_ID: METADATA_ID})
        assert metadata["finished"] is True
        assert "never recovers" in metadata["error"]

    def test_store_failure_during_finalize_still_wakes_waiters(self):
        # the cardinal sin would be a hung done event: a store that is
        # down exactly when a job fails must not stop finalization
        class ExplodingStore(InMemoryStore):
            def update_one(self, collection, query, new_values):
                raise ConnectionError("store mid-failover")

        store = ExplodingStore()
        store.insert_one(
            "ds", {ROW_ID: METADATA_ID, "filename": "ds", "finished": False}
        )
        manager = make_manager()

        def bad():
            raise ValueError("boom")

        manager.submit("doomed", bad, store=store, collection="ds")
        record = manager.wait("doomed", timeout=10)  # must NOT hang
        assert record.state == FAILED
        assert "boom" in record.error

    def test_terminal_failure_does_not_retry(self):
        manager = make_manager()
        calls = []

        def bad_input():
            calls.append(1)
            raise ValueError("not transient")

        manager.submit("bad", bad_input)
        record = manager.wait("bad", timeout=10)
        assert record.state == FAILED
        assert calls == [1]


# --------------------------------------------------------------------
# Deadlines and cancellation
# --------------------------------------------------------------------


class TestCancellation:
    def test_cancel_running_job_cooperatively(self):
        manager = make_manager()
        started = threading.Event()

        def spin():
            started.set()
            while True:
                check_cancelled()
                time.sleep(0.005)

        manager.submit("spin", spin)
        assert started.wait(10)
        assert manager.cancel("spin") == "cancelling"
        record = manager.wait("spin", timeout=10)
        assert record.state == CANCELLED
        assert manager.cancel("spin") == "terminal"
        assert manager.cancel("missing") == "unknown"

    def test_cancel_queued_job_never_runs(self):
        manager = make_manager(host_width=1, queue_cap=8)
        gate = threading.Event()
        ran = []
        manager.submit("hold", gate.wait)
        deadline = time.time() + 5
        while manager.get("hold").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        manager.submit("queued", ran.append, 1)
        assert manager.cancel("queued") == "cancelling"
        gate.set()
        record = manager.wait("queued", timeout=10)
        assert record.state == CANCELLED
        assert ran == []

    def test_cancelled_job_terminates_pollers(self):
        store = InMemoryStore()
        store.insert_one(
            "ds", {ROW_ID: METADATA_ID, "filename": "ds", "finished": False}
        )
        manager = make_manager()
        started = threading.Event()

        def spin():
            started.set()
            while True:
                check_cancelled()
                time.sleep(0.005)

        manager.submit("spin", spin, store=store, collection="ds")
        assert started.wait(10)
        manager.cancel("spin")
        manager.wait("spin", timeout=10)
        metadata = store.find_one("ds", {ROW_ID: METADATA_ID})
        assert metadata["finished"] is True

    def test_deadline_fails_queued_job_without_running(self):
        manager = make_manager(host_width=1, queue_cap=8)
        gate = threading.Event()
        ran = []
        manager.submit("hold", gate.wait)
        deadline = time.time() + 5
        while manager.get("hold").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        manager.submit("expiring", ran.append, 1, timeout=0.05)
        time.sleep(0.2)
        gate.set()
        record = manager.wait("expiring", timeout=10)
        assert record.state == FAILED
        assert "JobTimeoutError" in record.error
        assert ran == []

    def test_delete_route_cancels(self):
        from learningorchestra_tpu.services import database_api

        store = InMemoryStore()
        jobs = make_manager()
        client = database_api.create_app(store, jobs).test_client()
        started = threading.Event()

        def spin():
            started.set()
            while True:
                check_cancelled()
                time.sleep(0.005)

        jobs.submit("spin", spin)
        assert started.wait(10)
        assert client.delete("/jobs/spin").status_code == 202
        record = jobs.wait("spin", timeout=10)
        assert record.state == CANCELLED
        assert client.delete("/jobs/spin").status_code == 409
        assert client.delete("/jobs/missing").status_code == 404
        listing = body(client.get("/jobs"))["result"]
        (job,) = [j for j in listing if j["name"] == "spin"]
        assert job["state"] == "cancelled"
        assert job["job_class"] == HOST_CLASS


# --------------------------------------------------------------------
# Journal + recovery
# --------------------------------------------------------------------


class TestJournalRecovery:
    def test_journal_records_lifecycle(self):
        store = InMemoryStore()
        manager = make_manager(journal=JobJournal(store))
        manager.submit("ok", lambda: None)
        manager.wait("ok", timeout=10)
        events = [
            (doc["job"], doc["event"])
            for doc in store.find(JOURNAL_COLLECTION)
        ]
        assert events == [
            ("ok", "submitted"),
            ("ok", "started"),
            ("ok", "finished"),
        ]

    def test_ephemeral_sync_jobs_skip_the_journal(self):
        # run_sync with no replay op and no tracked collection: the
        # caller sees the outcome directly, recovery could only ever
        # mark it orphaned — journaling it is pure write amplification
        store = InMemoryStore()
        manager = make_manager(journal=JobJournal(store))
        manager.run_sync("ephemeral", lambda: None)
        assert list(store.find(JOURNAL_COLLECTION)) == []
        # a tracked sync job still journals (its pollers need recovery)
        store.insert_one(
            "ds", {ROW_ID: METADATA_ID, "filename": "ds", "finished": False}
        )
        manager.run_sync("tracked", lambda: None, store=store, collection="ds")
        events = [
            (doc["job"], doc["event"])
            for doc in store.find(JOURNAL_COLLECTION)
        ]
        assert ("tracked", "finished") in events
        assert all(job != "ephemeral" for job, _ in events)

    def test_replay_after_simulated_restart_leaves_no_hung_poller(
        self, tmp_path
    ):
        # The acceptance scenario: a "crashed" process left one job
        # RUNNING (orphan) and one admitted-but-never-started ingest.
        # After replay, NO collection may still read finished: false.
        store = InMemoryStore()
        journal = JobJournal(store)
        csv = tmp_path / "ok.csv"
        csv.write_text("a,b\n1,2\n3,4\n")
        for name in ("orphan_ds", "queued_ds"):
            store.insert_one(
                name,
                {ROW_ID: METADATA_ID, "filename": name, "finished": False},
            )
        journal.append(
            "build:orphan_ds",
            "submitted",
            job_class=DEVICE_CLASS,
            priority=0,
            collection="orphan_ds",
        )
        journal.append("build:orphan_ds", "started", attempt=1)
        journal.append(
            "ingest:queued_ds",
            "submitted",
            job_class=HOST_CLASS,
            priority=0,
            op="ingest",
            payload={"filename": "queued_ds", "url": str(csv)},
            collection="queued_ds",
        )
        # "restart": a fresh manager over the same store
        manager = make_manager(journal=JobJournal(store))
        outcome = recover_jobs(store, manager)
        assert outcome["orphaned"] == ["build:orphan_ds"]
        assert outcome["requeued"] == ["ingest:queued_ds"]
        orphan_meta = store.find_one("orphan_ds", {ROW_ID: METADATA_ID})
        assert orphan_meta["finished"] is True
        assert "orphaned" in orphan_meta["error"]
        record = manager.wait("ingest:queued_ds", timeout=30)
        assert record.state == FINISHED
        # recovery with live work is append-only (a crash mid-recovery
        # must never lose a job): the orphan got a terminal event, the
        # requeue a fresh submitted/started/finished tail
        events = [
            (doc["job"], doc["event"])
            for doc in store.find(JOURNAL_COLLECTION)
        ]
        assert ("ingest:queued_ds", "finished") in events
        assert ("build:orphan_ds", "orphaned") in events
        # the end state the reference can never reach: every dataset
        # metadata document terminated its pollers
        for name in ("orphan_ds", "queued_ds"):
            assert store.find_one(name, {ROW_ID: METADATA_ID})["finished"]
        # a SECOND restart finds everything terminal and no foreign
        # scopes → the journal compacts to nothing
        second = recover_jobs(
            store, make_manager(journal=JobJournal(store)), JobJournal(store)
        )
        assert second == {"requeued": [], "orphaned": []}
        assert list(store.find(JOURNAL_COLLECTION)) == []

    def test_scoped_recovery_leaves_other_scopes_alone(self):
        store = InMemoryStore()
        JobJournal(store, scope="database_api").append(
            "ingest:a", "submitted", op="ingest", payload={}
        )
        JobJournal(store, scope="model_builder").append(
            "build:b", "submitted", collection=None
        )
        manager = make_manager(
            journal=JobJournal(store, scope="model_builder")
        )
        outcome = recover_jobs(
            store, manager, JobJournal(store, scope="model_builder")
        )
        # build:b has no replay handler → terminal; ingest:a belongs to
        # database_api's scope and must be untouched
        assert outcome["orphaned"] == ["build:b"]
        assert outcome["requeued"] == []
        events = [
            (doc["job"], doc["event"], doc["scope"])
            for doc in store.find(JOURNAL_COLLECTION)
        ]
        assert ("ingest:a", "submitted", "database_api") in events
        assert ("build:b", "orphaned", "model_builder") in events

    def test_rejected_submission_is_terminal_in_journal(self):
        store = InMemoryStore()
        manager = make_manager(
            host_width=1, queue_cap=1, journal=JobJournal(store)
        )
        gate = threading.Event()
        manager.submit("hold", gate.wait)
        deadline = time.time() + 5
        while manager.get("hold").state == "pending":
            assert time.time() < deadline
            time.sleep(0.005)
        manager.submit("fill", lambda: None)
        with pytest.raises(QueueFullError):
            manager.submit("rejected", lambda: None)
        gate.set()
        manager.wait("fill", timeout=10)
        # a 429'd job must not be resurrected by the next restart
        fresh = make_manager(journal=JobJournal(store))
        outcome = recover_jobs(store, fresh, JobJournal(store))
        assert "rejected" not in outcome["requeued"]
        assert "rejected" not in outcome["orphaned"]


# --------------------------------------------------------------------
# End-to-end: device-class serialization over REST
# --------------------------------------------------------------------


class TestDeviceClassEndToEnd:
    @pytest.fixture()
    def titanic_like(self):
        store = InMemoryStore()
        for name in ("train_ds", "test_ds"):
            store.insert_one(
                name,
                {ROW_ID: METADATA_ID, "filename": name, "finished": True},
            )
        return store

    def test_concurrent_builds_never_overlap_on_the_mesh(
        self, titanic_like
    ):
        from learningorchestra_tpu.services import model_builder

        jobs = make_manager(device_width=1, queue_cap=2)
        in_flight = []
        max_in_flight = []
        lock = threading.Lock()

        def fake_build(builder_body: dict) -> None:
            with lock:
                in_flight.append(1)
                max_in_flight.append(len(in_flight))
            time.sleep(0.05)
            with lock:
                in_flight.pop()

        app = model_builder.create_app(
            titanic_like, build=fake_build, models_dir="", jobs=jobs
        )
        statuses = []

        # distinct job names per request (the job is named from the
        # test filename), so nothing 409s as a duplicate
        def post_named(index: int) -> None:
            name = f"test_ds{index}"
            titanic_like.insert_one(
                name,
                {ROW_ID: METADATA_ID, "filename": name, "finished": True},
            )
            client = app.test_client()
            response = client.post(
                "/models",
                json={
                    "training_filename": "train_ds",
                    "test_filename": name,
                    "preprocessor_code": "",
                    "classificators_list": ["nb"],
                    "async": True,
                },
            )
            statuses.append(response.status_code)

        threads = [
            threading.Thread(target=post_named, args=(i,)) for i in range(6)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=30)
        # every request was either admitted (201) or refused (429) —
        # nothing else — and admitted builds NEVER ran concurrently
        assert set(statuses) <= {201, 429}
        assert statuses.count(201) >= 1
        deadline = time.time() + 30
        while any(
            job["state"] in ("pending", "running")
            for job in jobs.all_jobs()
        ):
            assert time.time() < deadline
            time.sleep(0.01)
        assert max(max_in_flight) == 1

    def test_sync_build_queues_behind_async(self, titanic_like):
        from learningorchestra_tpu.services import model_builder

        jobs = make_manager(device_width=1, queue_cap=8)
        order = []

        def fake_build(builder_body: dict) -> None:
            order.append(builder_body["test_filename"])
            time.sleep(0.05)

        app = model_builder.create_app(
            titanic_like, build=fake_build, models_dir="", jobs=jobs
        )
        client = app.test_client()
        first = client.post(
            "/models",
            json={
                "training_filename": "train_ds",
                "test_filename": "test_ds",
                "preprocessor_code": "",
                "classificators_list": ["nb"],
                "async": True,
            },
        )
        assert first.status_code == 201
        # the sync build blocks until ITS turn on the device queue ends
        titanic_like.insert_one(
            "test_ds2",
            {ROW_ID: METADATA_ID, "filename": "test_ds2", "finished": True},
        )
        second = client.post(
            "/models",
            json={
                "training_filename": "train_ds",
                "test_filename": "test_ds2",
                "preprocessor_code": "",
                "classificators_list": ["nb"],
            },
        )
        assert second.status_code == 201
        assert order.index("test_ds") < order.index("test_ds2")


# --------------------------------------------------------------------
# Satellites: eviction, wait race, knob validation
# --------------------------------------------------------------------


class TestRecordEviction:
    def test_terminal_records_evicted_by_max_count(self, monkeypatch):
        monkeypatch.setenv("LO_JOB_HISTORY", "5")
        manager = make_manager()
        # lo_jobs_total is process-global: measure the delta, not the
        # absolute (other tests in this process increment it too)
        before = manager._jobs_total.value("finished")
        for index in range(12):
            manager.submit(f"job{index}", lambda: None)
            manager.wait(f"job{index}", timeout=10)
        assert len(manager.all_jobs()) <= 5
        # the counter stayed monotonic across evictions
        assert manager._jobs_total.value("finished") - before == 12.0

    def test_terminal_records_evicted_by_ttl(self, monkeypatch):
        monkeypatch.setenv("LO_JOB_TTL_S", "0.05")
        manager = make_manager()
        manager.submit("old", lambda: None)
        manager.wait("old", timeout=10)
        time.sleep(0.1)
        manager.submit("new", lambda: None)
        manager.wait("new", timeout=10)
        names = {job["name"] for job in manager.all_jobs()}
        assert "old" not in names
        assert "new" in names

    def test_active_jobs_never_evicted(self, monkeypatch):
        monkeypatch.setenv("LO_JOB_HISTORY", "2")
        manager = make_manager(host_width=4)
        gate = threading.Event()
        for index in range(4):
            manager.submit(f"live{index}", gate.wait)
        manager.submit("one_more", lambda: None)
        names = {job["name"] for job in manager.all_jobs()}
        assert {f"live{i}" for i in range(4)} <= names
        gate.set()
        for index in range(4):
            manager.wait(f"live{index}", timeout=10)


class TestWaitRace:
    def test_wait_returns_the_record_it_waited_on(self):
        manager = make_manager()
        manager.submit("job", lambda: None)
        first = manager.wait("job", timeout=10)
        assert first.state == FINISHED
        # re-register the same name with a never-finishing job; a wait
        # started BEFORE the re-registration must still return records
        # consistently (snapshot under the lock, not two racy reads)
        gate = threading.Event()
        manager.submit("job", gate.wait)
        with pytest.raises(TimeoutError):
            manager.wait("job", timeout=0.05)
        gate.set()
        assert manager.wait("job", timeout=10).state == FINISHED

    def test_wait_unknown_job_raises_keyerror(self):
        with pytest.raises(KeyError):
            make_manager().wait("ghost", timeout=0.1)


class TestKnobValidation:
    def test_malformed_values_fail_fast(self, monkeypatch):
        monkeypatch.setenv("LO_JOB_WORKERS", "eight")
        with pytest.raises(ValueError, match="LO_JOB_WORKERS"):
            sched_config.host_width()
        monkeypatch.setenv("LO_SCHED_DEVICE_WIDTH", "0")
        with pytest.raises(ValueError, match="LO_SCHED_DEVICE_WIDTH"):
            sched_config.device_width()
        monkeypatch.setenv("LO_SCHED_QUEUE_CAP", "-3")
        with pytest.raises(ValueError, match="LO_SCHED_QUEUE_CAP"):
            sched_config.queue_cap()

    def test_valid_values_apply(self, monkeypatch):
        monkeypatch.setenv("LO_JOB_WORKERS", "3")
        monkeypatch.setenv("LO_SCHED_DEVICE_WIDTH", "2")
        monkeypatch.setenv("LO_SCHED_QUEUE_CAP", "9")
        scheduler = Scheduler()
        assert scheduler.class_width(HOST_CLASS) == 3
        assert scheduler.class_width(DEVICE_CLASS) == 2
        assert scheduler._classes[HOST_CLASS].cap == 9

    def test_cluster_manifest_sched_section(self, tmp_path):
        import sys

        sys.path.insert(0, "deploy")
        try:
            import cluster
        finally:
            sys.path.pop(0)
        path = tmp_path / "manifest.json"
        path.write_text(
            json.dumps(
                {
                    "repo": ".",
                    "head": {"host": "127.0.0.1"},
                    "sched": {"job_workers": 4, "queue_cap": 32},
                }
            )
        )
        loaded = cluster.load_manifest(str(path))
        env = cluster.machine_plans(loaded)[0]["env"]
        assert env["LO_JOB_WORKERS"] == "4"
        assert env["LO_SCHED_QUEUE_CAP"] == "32"
        bad = tmp_path / "bad.json"
        for value in ("four", 0, True):  # bool is an int subclass
            bad.write_text(
                json.dumps(
                    {
                        "repo": ".",
                        "head": {"host": "127.0.0.1"},
                        "sched": {"job_workers": value},
                    }
                )
            )
            with pytest.raises(SystemExit):
                cluster.load_manifest(str(bad))
