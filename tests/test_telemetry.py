"""End-to-end telemetry: the metrics registry, Prometheus rendering,
and correlation-ID span propagation REST → job → span tree.

Covers the acceptance surface of the telemetry layer: registry
concurrency, a rendering golden, /metrics on all seven services, the
PhaseTimer→span bridge, the SPMD correlation envelope, and a model
build whose trace phase durations account for the job's wall-clock."""

import threading
import time

import pytest

from learningorchestra_tpu.core.ingest import ingest_csv, write_ingest_metadata
from learningorchestra_tpu.core.jobs import JobManager
from learningorchestra_tpu.ops.dtype import convert_field_types
from learningorchestra_tpu.services import database_api, model_builder
from learningorchestra_tpu.services.runner import build_apps
from learningorchestra_tpu.telemetry import metrics as metrics_mod
from learningorchestra_tpu.telemetry import tracing
from learningorchestra_tpu.telemetry.metrics import MetricsRegistry
from learningorchestra_tpu.utils.profiling import PhaseTimer


class TestRegistry:
    def test_counter_gauge_histogram_concurrency(self):
        registry = MetricsRegistry()
        counter = registry.counter("t_ops_total", "ops", labels=("kind",))
        gauge = registry.gauge("t_depth", "depth")
        hist = registry.histogram("t_secs", "secs", buckets=(0.5, 1.0))
        threads = [
            threading.Thread(
                target=lambda: [
                    (
                        counter.labels("a").inc(),
                        gauge.inc(),
                        hist.observe(0.25),
                    )
                    for _ in range(1000)
                ]
            )
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert counter.value("a") == 8000
        assert gauge.value() == 8000
        text = registry.render()
        assert 't_secs_bucket{le="0.5"} 8000' in text
        assert "t_secs_count 8000" in text

    def test_redeclaration_is_get_or_create(self):
        registry = MetricsRegistry()
        a = registry.counter("t_same", "x", labels=("l",))
        b = registry.counter("t_same", "x", labels=("l",))
        assert a is b
        with pytest.raises(ValueError):
            registry.gauge("t_same", "x", labels=("l",))
        with pytest.raises(ValueError):
            registry.counter("t_same", "x", labels=("other",))

    def test_prometheus_rendering_golden(self):
        registry = MetricsRegistry()
        c = registry.counter("t_req_total", "requests", labels=("svc",))
        c.labels("db").inc(3)
        g = registry.gauge("t_up", "liveness")
        g.set(1)
        h = registry.histogram("t_lat", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        assert registry.render() == (
            "# HELP t_lat latency\n"
            "# TYPE t_lat histogram\n"
            't_lat_bucket{le="0.1"} 1\n'
            't_lat_bucket{le="1"} 1\n'
            't_lat_bucket{le="+Inf"} 2\n'
            "t_lat_sum 5.05\n"
            "t_lat_count 2\n"
            "# HELP t_req_total requests\n"
            "# TYPE t_req_total counter\n"
            't_req_total{svc="db"} 3\n'
            "# HELP t_up liveness\n"
            "# TYPE t_up gauge\n"
            "t_up 1\n"
        )

    def test_label_escaping(self):
        registry = MetricsRegistry()
        c = registry.counter("t_esc", "x", labels=("p",))
        c.labels('a"b\\c\nd').inc()
        assert 't_esc{p="a\\"b\\\\c\\nd"} 1' in registry.render()

    def test_collector_failure_does_not_break_render(self):
        registry = MetricsRegistry()
        registry.gauge("t_ok", "x").set(7)

        def bad(_registry):
            raise RuntimeError("boom")

        registry.register_collector(bad)
        assert "t_ok 7" in registry.render()


class TestMemoryGauges:
    """The device and resident-set gauges: set by one collector at
    scrape time, present only where the platform gives a figure."""

    def test_process_gauges_on_the_global_registry(self):
        text = metrics_mod.global_registry().render()
        values = {}
        for line in text.splitlines():
            if line.startswith("lo_process_"):
                name, value = line.split()
                values[name] = float(value)
        assert values["lo_process_resident_bytes"] > 2**20
        assert values["lo_process_peak_resident_bytes"] >= (
            values["lo_process_resident_bytes"]
        )

    def test_device_gauges_when_the_device_reports(self, monkeypatch):
        monkeypatch.setattr(
            metrics_mod,
            "_device_memory",
            lambda: {"bytes_in_use": 3 * 2**30, "peak_bytes_in_use": 5 * 2**30},
        )
        registry = MetricsRegistry()
        registry.register_collector(metrics_mod._collect_memory)
        text = registry.render()
        assert f"lo_device_bytes_in_use {3 * 2**30}" in text
        assert f"lo_device_peak_bytes_in_use {5 * 2**30}" in text

    def test_no_figure_no_gauge(self, monkeypatch):
        # a CPU backend's memory_stats() is None, a platform without
        # /proc has no resident set: the families are left out, not 0
        monkeypatch.setattr(metrics_mod, "_device_memory", lambda: {})
        monkeypatch.setattr(metrics_mod, "_process_memory", lambda: {})
        registry = MetricsRegistry()
        registry.register_collector(metrics_mod._collect_memory)
        text = registry.render()
        assert "lo_device_" not in text and "lo_process_" not in text

    def test_peak_falls_back_to_rusage_where_proc_has_no_high_water_mark(
        self, monkeypatch
    ):
        # the machine with the chip lists VmRSS and no VmHWM
        import builtins
        import io
        import resource

        real_open = builtins.open

        def fake_open(path, *args, **kwargs):
            if path == "/proc/self/status":
                return io.StringIO("Name:\tpython\nVmRSS:\t    2048 kB\n")
            return real_open(path, *args, **kwargs)

        monkeypatch.setattr(builtins, "open", fake_open)
        figures = metrics_mod._process_memory()
        assert figures["VmRSS"] == 2048 * 1024
        assert figures["VmHWM"] == (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        ) > 0

    def test_fullest_local_device_is_the_one_reported(self, monkeypatch):
        import jax

        class Device:
            def __init__(self, stats):
                self._stats = stats

            def memory_stats(self):
                return self._stats

        jax.devices()  # a backend is up, as in every serving process
        monkeypatch.setattr(
            jax,
            "local_devices",
            lambda: [
                Device({"bytes_in_use": 10, "peak_bytes_in_use": 90}),
                Device({"bytes_in_use": 40, "peak_bytes_in_use": 50}),
                Device(None),
            ],
        )
        assert metrics_mod._device_memory() == {
            "bytes_in_use": 40, "peak_bytes_in_use": 50,
        }

    @pytest.mark.parametrize(
        "prelude, check",
        [
            # a store-only process never imports jax: a scrape must not
            ("", "assert 'jax' not in sys.modules"),
            # jax imported, no backend yet: a scrape must not start one
            (
                "import jax",
                "from jax._src import xla_bridge; "
                "assert not xla_bridge.backends_are_initialized()",
            ),
        ],
    )
    def test_a_scrape_starts_no_backend(self, prelude, check):
        import os
        import subprocess
        import sys

        code = (
            f"import sys; {prelude or 'pass'}\n"
            "from learningorchestra_tpu.telemetry.metrics import "
            "global_registry\n"
            "text = global_registry().render()\n"
            "assert 'lo_process_resident_bytes' in text\n"
            "assert 'lo_device_' not in text\n"
            f"{check}\n"
        )
        done = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert done.returncode == 0, done.stderr[-2000:]


class TestTracing:
    def test_span_noop_without_trace(self):
        with tracing.span("orphan") as s:
            assert s is None

    def test_nesting_and_thread_attach(self):
        trace = tracing.Trace("cid01")
        with tracing.activate(trace):
            with tracing.span("outer"):
                with tracing.span("inner"):
                    pass
                context = tracing.capture()

                def worker():
                    with tracing.attach(context), tracing.span("threaded"):
                        pass

                t = threading.Thread(target=worker)
                t.start()
                t.join()
        tree = trace.as_dict()
        assert tree["correlation_id"] == "cid01"
        (outer,) = tree["spans"]
        names = {child["name"] for child in outer["children"]}
        assert names == {"inner", "threaded"}

    def test_phase_timer_bridges_to_spans(self):
        timer = PhaseTimer()
        trace = tracing.Trace("cid02")
        with tracing.activate(trace):
            with timer.phase("fit"):
                time.sleep(0.01)
        assert timer.timings["fit"] > 0
        (span_dict,) = [s.as_dict() for s in trace.spans]
        assert span_dict["name"] == "phase:fit"
        # same clock, same window: the span IS the phase
        assert abs(span_dict["duration_s"] - timer.timings["fit"]) < 0.01

    def test_phase_timer_without_trace_still_times(self):
        timer = PhaseTimer()
        with timer.phase("solo"):
            pass
        assert "solo" in timer.timings


class TestRestSurface:
    def test_metrics_on_all_seven_services(self, store, tmp_path):
        apps = build_apps(store, str(tmp_path / "images"))
        assert len(apps) == 7
        for port, app in apps.items():
            client = app.test_client()
            response = client.get("/metrics")
            assert response.status_code == 200, app.name
            assert response.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4"
            )
            text = response.get_data(as_text=True)
            for family in (
                "lo_http_requests_total",
                "lo_jobs_running",
                "lo_jitcache_persistent_hits",
                "lo_store_collections",
            ):
                assert family in text, (app.name, family)

    def test_request_metrics_and_correlation_header(self, store):
        app = database_api.create_app(store, JobManager())
        client = app.test_client()
        minted = client.get("/files").headers["X-Correlation-Id"]
        assert len(minted) == 16
        echoed = client.get(
            "/files", headers={"X-Correlation-Id": "fixed0123"}
        ).headers["X-Correlation-Id"]
        assert echoed == "fixed0123"
        text = client.get("/metrics").get_data(as_text=True)
        assert (
            'lo_http_requests_total{service="database_api",route="/files",'
            'method="GET",status="200"}'
        ) in text
        assert "lo_http_request_duration_seconds_bucket" in text

    def test_ingest_job_trace_carries_request_correlation_id(
        self, store, titanic_csv
    ):
        jobs = JobManager()
        client = database_api.create_app(store, jobs).test_client()
        response = client.post(
            "/files",
            json={"filename": "titanic", "url": titanic_csv},
            headers={"X-Correlation-Id": "ingest01"},
        )
        assert response.status_code == 201
        jobs.wait("ingest:titanic", timeout=30)
        payload = client.get("/jobs/ingest:titanic/trace").get_json()["result"]
        assert payload["correlation_id"] == "ingest01"
        assert payload["trace"]["correlation_id"] == "ingest01"
        (root,) = payload["trace"]["spans"]
        assert root["name"] == "job:ingest:titanic"
        assert root["duration_s"] > 0
        listing = client.get("/jobs").get_json()["result"]
        assert listing[0]["correlation_id"] == "ingest01"

    def test_unknown_job_trace_404(self, store):
        client = database_api.create_app(store, JobManager()).test_client()
        assert client.get("/jobs/nope/trace").status_code == 404


NUMERIC_FIELDS = (
    "PassengerId", "Survived", "Pclass", "Age", "SibSp", "Parch", "Fare"
)


@pytest.fixture()
def titanic_store(store, titanic_csv):
    for name in ("titanic_train", "titanic_test"):
        write_ingest_metadata(store, name, titanic_csv)
        ingest_csv(store, name, titanic_csv)
        convert_field_types(
            store, name, {f: "number" for f in NUMERIC_FIELDS}
        )
    return store


class TestBuildTrace:
    def test_sync_build_trace_phases_cover_wall_clock(self, titanic_store):
        from tests.test_frame import DOCUMENTED_PREPROCESSOR

        jobs = JobManager()
        app = model_builder.create_app(
            titanic_store, models_dir="", jobs=jobs
        )
        client = app.test_client()
        response = client.post(
            "/models",
            json={
                "training_filename": "titanic_train",
                "test_filename": "titanic_test",
                "preprocessor_code": DOCUMENTED_PREPROCESSOR,
                "classificators_list": ["nb"],
            },
            headers={"X-Correlation-Id": "build001"},
        )
        assert response.status_code == 201
        assert response.get_json() == {"result": "created_file"}
        payload = client.get(
            "/jobs/build:titanic_test:nb/trace"
        ).get_json()["result"]
        assert payload["state"] == "finished"
        assert payload["correlation_id"] == "build001"
        (root,) = payload["trace"]["spans"]
        assert root["name"] == "job:build:titanic_test:nb"
        stages = {child["name"]: child for child in root["children"]}
        assert {"load_data", "preprocess", "train:nb"} <= set(stages)
        phases = {
            grandchild["name"]
            for grandchild in stages["train:nb"]["children"]
        }
        assert {"phase:fit", "phase:evaluate", "phase:write"} <= phases
        # acceptance: stage durations sum to within 10% of the job's
        # wall-clock (single classifier — no concurrent-span overlap).
        # abs floor: on a fully warm cache the whole build is ~25 ms and
        # the constant pool-spinup overhead (~3 ms) would exceed 10% of
        # a job that small — the criterion is about minutes-long builds.
        wall = payload["ended_at"] - payload["started_at"]
        covered = sum(child["duration_s"] for child in root["children"])
        assert covered == pytest.approx(wall, rel=0.10, abs=0.05)

    def test_failing_sync_build_runs_once_and_surfaces_error(
        self, titanic_store
    ):
        # run_sync re-raises the build's own ValueError; the handler
        # must not mistake it for "job already active" and rerun the
        # build (the double-execution would duplicate partial writes)
        calls = []

        def exploding_build(body):
            calls.append(1)
            raise ValueError("ragged columns")

        client = model_builder.create_app(
            titanic_store, build=exploding_build, models_dir=""
        ).test_client()
        response = client.post(
            "/models",
            json={
                "training_filename": "titanic_train",
                "test_filename": "titanic_test",
                "preprocessor_code": "",
                "classificators_list": ["nb"],
            },
        )
        assert response.status_code == 500
        assert b"ragged columns" in response.get_data()
        assert calls == [1]

    def test_async_build_gets_same_trace(self, titanic_store):
        from tests.test_frame import DOCUMENTED_PREPROCESSOR

        jobs = JobManager()
        client = model_builder.create_app(
            titanic_store, models_dir="", jobs=jobs
        ).test_client()
        response = client.post(
            "/models",
            json={
                "training_filename": "titanic_train",
                "test_filename": "titanic_test",
                "preprocessor_code": DOCUMENTED_PREPROCESSOR,
                "classificators_list": ["nb"],
                "async": True,
            },
            headers={"X-Correlation-Id": "build002"},
        )
        assert response.status_code == 201
        jobs.wait("build:titanic_test:nb", timeout=120)
        payload = client.get(
            "/jobs/build:titanic_test:nb/trace"
        ).get_json()["result"]
        assert payload["correlation_id"] == "build002"
        (root,) = payload["trace"]["spans"]
        assert any(
            child["name"] == "train:nb" for child in root["children"]
        )


class TestSpmdTelemetry:
    def test_single_process_submit_spans_and_metrics(self):
        from learningorchestra_tpu.parallel.spmd import SpmdDispatcher

        dispatcher = SpmdDispatcher()
        dispatcher.register("noop", lambda payload: payload["x"])
        trace = tracing.Trace("spmd0001")
        with tracing.activate(trace):
            assert dispatcher.submit("noop", {"x": 41}) == 41
        (span_dict,) = [s.as_dict() for s in trace.spans]
        assert span_dict["name"] == "spmd:noop"
        registry = metrics_mod.global_registry()
        assert registry.counter(
            "lo_spmd_jobs_total", "", labels=("op", "outcome")
        ).value("noop", "ok") >= 1

    def test_worker_loop_attributes_broadcast_cid(self, monkeypatch):
        from learningorchestra_tpu.parallel import spmd

        jobs = iter(
            [
                {"op": "work", "payload": {}, "cid": "bcast001"},
                {"op": "__shutdown__"},
            ]
        )
        monkeypatch.setattr(
            spmd, "_broadcast_json", lambda obj=None: next(jobs)
        )
        seen = {}

        def handler(payload):
            seen["cid"] = tracing.current_correlation_id()

        dispatcher = spmd.SpmdDispatcher()
        dispatcher.register("work", handler)
        dispatcher.run_worker_loop()
        # the worker ran under the COORDINATOR's correlation id...
        assert seen["cid"] == "bcast001"
        # ...and parked the finished trace for operator dumps
        remembered = tracing.recall_trace("bcast001")
        (span_dict,) = [s.as_dict() for s in remembered.spans]
        assert span_dict["name"] == "spmd:work"


class TestStoreTelemetry:
    def test_telemetry_stats_shape(self, store):
        store.insert_one("c1", {"a": 1})
        stats = store.telemetry_stats()
        assert stats["collections"] == 1
        assert stats["wal_bytes"] == 0  # pure in-memory store: no WAL
        assert stats["spill_bytes"] == 0

    def test_wal_bytes_reported(self, tmp_path):
        from learningorchestra_tpu.core.store import InMemoryStore

        durable = InMemoryStore(data_dir=str(tmp_path))
        durable.insert_one("c1", {"a": 1})
        assert durable.telemetry_stats()["wal_bytes"] > 0

    def test_resync_apply_reclaims_spill_folders(self, tmp_path):
        from learningorchestra_tpu.core.store import InMemoryStore

        follower = InMemoryStore(replicate=True)
        spill = tmp_path / "spill" / "c1.0"
        spill.mkdir(parents=True)
        (spill / "col.bin").write_bytes(b"x" * 64)
        follower._spill_folders["c1"] = str(spill)
        assert follower.telemetry_stats()["spill_bytes"] == 64
        follower.resync_apply([])
        # the leak: resync cleared collections but stranded the folder
        # mapping and the on-disk files
        assert follower._spill_folders == {}
        assert not spill.exists()
        assert follower.telemetry_stats()["spill_bytes"] == 0
