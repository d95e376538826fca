"""Flight recorder (telemetry/profile.py): Chrome trace export golden,
byte-flow attribution, the sampling profiler under concurrency, the
/profile REST surface and PhaseTimer per-occurrence boundaries."""

import json
import os
import threading
import time

import pytest

from learningorchestra_tpu.core.devcache import reset_global_devcache
from learningorchestra_tpu.core.ingest import ingest_csv, write_ingest_metadata
from learningorchestra_tpu.core.jobs import JobManager
from learningorchestra_tpu.ops.dtype import convert_field_types
from learningorchestra_tpu.services import model_builder
from learningorchestra_tpu.telemetry import profile, tracing
from learningorchestra_tpu.utils.profiling import PhaseTimer
from learningorchestra_tpu.utils.web import WebApp

NUMERIC_FIELDS = (
    "PassengerId", "Survived", "Pclass", "Age", "SibSp", "Parch", "Fare"
)

FIVE = ["lr", "dt", "rf", "gb", "nb"]


@pytest.fixture(scope="module")
def built_client(tmp_path_factory):
    """ONE 5-classifier build shared by every export test in this
    module. Module-scoped and fan-out-serialized (LO_BUILD_WORKERS=1)
    on purpose: XLA's CPU backend can rendezvous-deadlock when two
    already-compiled collective programs execute concurrently on the
    8 virtual devices (two evals each holding part of the device
    thread pool — a test-environment artifact, not a product path:
    real dispatches serialize through the device queue). One cold
    build with a serialized pool never hits it; the write-back worker
    still gives the timeline its second thread row."""
    import os

    from tests.conftest import TITANIC_LIKE_CSV
    from tests.test_frame import DOCUMENTED_PREPROCESSOR
    from learningorchestra_tpu.core.store import InMemoryStore

    csv_path = tmp_path_factory.mktemp("profile") / "titanic.csv"
    csv_path.write_text(TITANIC_LIKE_CSV)
    reset_global_devcache()  # the h2d spans below need a COLD cache
    store = InMemoryStore()
    for name in ("titanic_train", "titanic_test"):
        write_ingest_metadata(store, name, str(csv_path))
        ingest_csv(store, name, str(csv_path))
        convert_field_types(
            store, name, {f: "number" for f in NUMERIC_FIELDS}
        )
    client = model_builder.create_app(
        store, models_dir="", jobs=JobManager()
    ).test_client()
    previous = os.environ.get("LO_BUILD_WORKERS")
    os.environ["LO_BUILD_WORKERS"] = "1"
    try:
        response = client.post(
            "/models",
            json={
                "training_filename": "titanic_train",
                "test_filename": "titanic_test",
                "preprocessor_code": DOCUMENTED_PREPROCESSOR,
                "classificators_list": FIVE,
            },
        )
    finally:
        if previous is None:
            os.environ.pop("LO_BUILD_WORKERS", None)
        else:
            os.environ["LO_BUILD_WORKERS"] = previous
    assert response.status_code == 201
    return client


class TestChromeTraceExport:
    def test_five_classifier_build_profile_golden(self, built_client):
        """Acceptance: the completed 5-classifier build's /profile is
        valid Chrome trace-event JSON whose spans carry the required
        ph/ts/dur/tid fields, whose phase spans carry byte/row
        attribution, and whose byte counter tracks are present."""
        response = built_client.get(
            f"/jobs/build:titanic_test:{'+'.join(FIVE)}/profile"
        )
        assert response.status_code == 200
        trace = json.loads(response.data)  # valid JSON end to end
        events = trace["traceEvents"]
        complete = [e for e in events if e["ph"] == "X"]
        assert complete, "no span events exported"
        for event in complete:
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(event)
            assert event["dur"] >= 0 and event["ts"] >= 0
        names = {event["name"] for event in complete}
        assert {"load_data", "preprocess"} <= names
        for classifier in FIVE:
            assert f"train:{classifier}" in names
        # one row per thread: the 5-way classifier pool means >1 tid
        assert len({event["tid"] for event in complete}) > 1
        # byte counter tracks present and monotonically accumulating
        counters = [e for e in events if e["ph"] == "C"]
        assert counters, "no byte counter track"
        h2d_series = [c["args"]["h2d"] for c in counters]
        assert h2d_series == sorted(h2d_series)
        assert h2d_series[-1] > 0
        # phase spans carry byte/row attribution: the h2d transfers sum
        # to (at least) the rows actually moved — 8 CSV rows minus the
        # one NaN-age row, times features, times 4 bytes f32
        h2d_spans = [
            e for e in complete
            if e["name"].startswith("h2d:") and e.get("args")
        ]
        assert h2d_spans
        moved_rows = max(e["args"].get("rows", 0) for e in h2d_spans)
        assert moved_rows >= 7
        total = trace["otherData"]["bytes_total"]
        assert total["h2d_bytes"] >= moved_rows * 4
        # write phases carry bytes + rows of the persisted predictions
        writes = [e for e in complete if e["name"] == "phase:write"]
        assert len(writes) == len(FIVE)
        assert all(
            e["args"]["bytes"] > 0 and e["args"]["rows"] >= 7
            for e in writes
        )

    def test_profile_summary_format(self, built_client):
        response = built_client.get(
            f"/jobs/build:titanic_test:{'+'.join(FIVE)}/profile"
            "?format=summary"
        )
        assert response.status_code == 200
        summary = response.get_json()["result"]
        assert summary["job"]["state"] == "finished"
        phases = summary["phases"]
        assert phases["phase:fit"]["count"] == len(FIVE)
        assert phases["phase:fit"]["seconds"] > 0
        assert phases["phase:write"]["bytes"]["payload"] > 0
        # rows attribution yields rows/s for the fit phase
        assert phases["phase:fit"].get("rows_per_s", 0) > 0

    def test_profile_404_for_unknown_job(self, built_client):
        assert built_client.get("/jobs/nope/profile").status_code == 404
        assert (
            built_client.get(
                "/jobs/nope/profile?format=summary"
            ).status_code
            == 404
        )


class TestWireAttribution:
    def test_remote_read_span_carries_wire_bytes_and_decode(self):
        from learningorchestra_tpu.core.store import InMemoryStore
        from learningorchestra_tpu.core.store_service import (
            RemoteStore,
            create_store_app,
        )
        from learningorchestra_tpu.utils.web import ServerThread

        server = ServerThread(
            create_store_app(InMemoryStore()), "127.0.0.1", 0
        ).start()
        try:
            remote = RemoteStore(f"http://127.0.0.1:{server.port}")
            remote.create_collection("wired")
            rows = list(range(500))
            trace = tracing.Trace(name="wire")
            with tracing.activate(trace):
                remote.insert_columns(
                    "wired", {"x": rows, "y": rows}, start_id=1
                )
                arrays = remote.read_column_arrays("wired")
            assert len(arrays["x"]) == 500
            tree = trace.as_dict()
            spans = {s["name"]: s for s in tree["spans"]}
            write = spans["wire:write"]
            assert write["meta"]["rows"] == 500
            assert write["meta"]["wire_bytes"] > 500 * 8
            read = spans["wire:read"]
            assert read["meta"]["rows"] == 500
            assert read["meta"]["wire_bytes"] > 500 * 8
            assert read["meta"]["decode_s"] > 0
            assert read["meta"]["collection"] == "wired"
            # and the chrome export shows the wire counter moving
            chrome = profile.chrome_trace(trace)
            assert chrome["otherData"]["bytes_total"]["wire_bytes"] >= (
                read["meta"]["wire_bytes"]
            )
        finally:
            server.stop()


class TestPhaseTimerEntries:
    def test_reentrant_phase_keeps_boundaries_and_summed_metadata(self):
        timer = PhaseTimer()
        trace = tracing.Trace(name="phases")
        with tracing.activate(trace):
            with timer.phase("fit", rows=10):
                time.sleep(0.02)
            with timer.phase("fit", rows=20):
                time.sleep(0.03)
        # as_metadata keeps the summed contract
        assert timer.as_metadata()["fit"] == pytest.approx(0.05, abs=0.04)
        # but the boundaries survive: two entries, two spans
        spans = [s for s in trace.as_dict()["spans"] if s["name"] == "phase:fit"]
        assert len(spans) == 2
        # distinct windows, and together the summed total
        assert spans[1]["start_ts"] >= (
            spans[0]["start_ts"] + spans[0]["duration_s"] * 0.5
        )
        assert spans[0]["duration_s"] + spans[1]["duration_s"] == (
            pytest.approx(timer.timings["fit"], abs=0.005)
        )
        assert spans[0]["meta"]["rows"] == 10
        assert spans[1]["meta"]["rows"] == 20
        assert spans[0]["start_ts"] + spans[0]["duration_s"] <= (
            spans[1]["start_ts"] + 0.01
        )
        # the timeline export keeps them as two events
        events = [
            e
            for e in profile.chrome_trace(trace)["traceEvents"]
            if e["ph"] == "X" and e["name"] == "phase:fit"
        ]
        assert len(events) == 2


def _walk(span, path=()):
    here = path + (span["name"],)
    yield span, here
    for child in span.get("children", []):
        yield from _walk(child, here)


@pytest.fixture(scope="module")
def build_spans(built_client):
    """``[(span, path)]`` of the shared five-classifier build's trace,
    as ``GET /jobs/<name>/trace`` serves it."""
    response = built_client.get(
        f"/jobs/build:titanic_test:{'+'.join(FIVE)}/trace"
    )
    assert response.status_code == 200
    trace = json.loads(response.data)["result"]["trace"]
    return [
        pair for root in trace["spans"] for pair in _walk(root)
    ]


def _input_span(names):
    """Of a fit's children, the one that stands for its training
    input: ``h2d:train`` where the fit made the build's device copy,
    ``fit:input`` where it waited for it."""
    (found,) = [name for name in names if name in ("h2d:train", "fit:input")]
    return found


def _named(spans, name, under=None):
    return [
        (span, path)
        for span, path in spans
        if span["name"] == name and (under is None or under in path)
    ]


class TestBuildSpanTree:
    """Where the host work, the transfers and the device waits of a
    build stand in its trace (ISSUE 26): each span under the parent
    that the benchmark's readers expect."""

    @pytest.mark.parametrize("classifier", ["dt", "rf", "gb"])
    def test_tree_fit_has_its_threshold_pass(self, build_spans, classifier):
        found = _named(build_spans, "fit:thresholds", f"train:{classifier}")
        assert len(found) == 1
        span, path = found[0]
        assert path[-3:] == (
            f"train:{classifier}", "phase:fit", "fit:thresholds"
        )
        assert span["meta"]["rows"] >= 7
        assert span["meta"]["features"] >= 1
        assert span["meta"]["bins"] == 32
        assert span["meta"]["passes"] in (0, 1)
        # the pass reads the build's device copy of the matrix, which
        # this fit made or waited for
        (fit, _), = _named(build_spans, "phase:fit", f"train:{classifier}")
        names = [child["name"] for child in fit["children"]]
        assert names.index(_input_span(names)) < names.index(
            "fit:thresholds"
        ) < names.index("fit:enqueue")

    def test_one_threshold_pass_serves_the_three_tree_fits(self, build_spans):
        found = _named(build_spans, "fit:thresholds")
        assert len(found) == 3
        assert sum(span["meta"]["passes"] for span, _ in found) == 1

    @pytest.mark.parametrize("classifier", ["lr", "nb"])
    def test_no_threshold_pass_outside_the_trees(
        self, build_spans, classifier
    ):
        assert not _named(
            build_spans, "fit:thresholds", f"train:{classifier}"
        )

    def test_lr_fit_splits_into_standardize_h2d_and_segments(
        self, build_spans
    ):
        (fit, _), = _named(build_spans, "phase:fit", "train:lr")
        children = [
            child["name"] for child in fit["children"]
            if not child["name"].startswith("compile:")
        ]
        # the build's float32 copy crosses (or is waited for), then is
        # standardised into a buffer of LR's own
        assert children[:3] == [
            _input_span(children), "fit:standardize", "fit:enqueue"
        ]
        segments = [
            child for child in fit["children"]
            if child["name"] == "fit:segment"
        ]
        assert segments
        # what is enqueued outside the segments, then the wait
        assert children[-2:] == ["fit:enqueue", "fit:device_wait"]
        # the iteration count on the fit is the segments' own
        iterations = fit["meta"]["lbfgs_iterations"]
        assert iterations == sum(
            segment["meta"]["iters"] for segment in segments
        )
        assert 0 < iterations <= 100
        # the passes over the matrix: the margins at each segment's
        # entry, the gradient at the first's, two an iteration
        assert fit["meta"]["lbfgs_matrix_passes"] == (
            len(segments) + 1 + 2 * iterations
        )

    @pytest.mark.parametrize("classifier", FIVE)
    def test_fit_children_never_outlast_the_fit(
        self, build_spans, classifier
    ):
        (fit, _), = _named(build_spans, "phase:fit", f"train:{classifier}")
        names = [child["name"] for child in fit["children"]]
        assert names.count("fit:device_wait") == 1
        assert names.index(_input_span(names)) < names.index(
            "fit:enqueue"
        ) < names.index("fit:device_wait")
        # one thread, one after the other: the children's seconds are
        # part of the fit's, never more (compile spans are recorded
        # after the fact and may overlap, so they are left out)
        timed = [
            child for child in fit["children"]
            if not child["name"].startswith("compile:")
        ]
        assert sum(child["duration_s"] for child in timed) <= (
            fit["duration_s"] + 1e-3
        )
        for child in timed:
            assert child["start_ts"] >= fit["start_ts"] - 1e-3
            assert child["start_ts"] + child["duration_s"] <= (
                fit["start_ts"] + fit["duration_s"] + 1e-3
            )

    @pytest.mark.parametrize("classifier", FIVE)
    def test_fit_h2d_span_carries_the_bytes(self, build_spans, classifier):
        # one copy a build, made by the first fit to ask (lr: the
        # fan-out is serialised here); every other fit waits for it
        (span, path), = _named(build_spans, "h2d:train")
        assert span["meta"]["copies"] == 1
        # at least the float32 matrix of the 7 usable rows
        assert span["meta"]["h2d_bytes"] >= span["meta"]["rows"] * 4
        waits = _named(build_spans, "fit:input", f"train:{classifier}")
        if f"train:{classifier}" in path:
            assert not waits
        else:
            (wait, _), = waits
            assert wait["meta"]["rows"] == span["meta"]["rows"]
            assert "h2d_bytes" not in wait["meta"]
        (fit, _), = _named(build_spans, "phase:fit", f"train:{classifier}")
        assert "h2d_bytes" not in fit.get("meta", {})

    @pytest.mark.parametrize("classifier", FIVE)
    def test_evaluate_waits_for_the_device_before_the_copy(
        self, build_spans, classifier
    ):
        (evaluate, _), = _named(
            build_spans, "phase:evaluate", f"train:{classifier}"
        )
        names = [child["name"] for child in evaluate["children"]]
        assert names.count("eval:device_wait") == 1
        assert names.index("eval:enqueue") + 1 == names.index(
            "eval:device_wait"
        )
        assert names.index("eval:device_wait") + 1 == names.index(
            "d2h:predictions"
        )

    def test_assembler_spans_stand_under_preprocess(self, build_spans):
        found = _named(build_spans, "frame:assemble")
        assert len(found) >= 2  # one per transform of the preprocessor
        for span, path in found:
            assert path[-2] == "preprocess"
            meta = span["meta"]
            assert meta["bytes"] == meta["rows"] * meta["features"] * 8

    def test_no_devices_span(self, build_spans):
        assert not _named(build_spans, "devices")


class TestDeviceWait:
    def test_blocks_under_a_named_span_and_returns_the_tree(self):
        import jax.numpy as jnp

        trace = tracing.Trace(name="wait")
        with tracing.activate(trace):
            out = tracing.device_wait("fit:device_wait", [jnp.ones(4) * 2])
        assert float(out[0][0]) == 2.0
        (span,) = trace.as_dict()["spans"]
        assert span["name"] == "fit:device_wait"
        assert span["duration_s"] is not None

    def test_no_trace_no_span(self):
        import jax.numpy as jnp

        assert tracing.current_trace() is None
        out = tracing.device_wait("fit:device_wait", jnp.zeros(2))
        assert out.shape == (2,)


class TestFitAttribution:
    """The same spans on direct library calls, where the counters can
    be read round one fit."""

    def _data(self):
        import numpy as np

        rng = np.random.default_rng(11)
        X = rng.normal(size=(96, 5)).astype(np.float32)
        y = (X[:, 0] + X[:, 1] > 0).astype(np.int32)
        return X, y

    def test_h2d_train_bytes_equal_the_counters_growth(self):
        from learningorchestra_tpu.ml.base import make_classifier

        X, y = self._data()
        trace = tracing.Trace(name="fit")
        before = profile.flow_totals()["h2d_bytes"]
        with tracing.activate(trace), tracing.span("phase:fit"):
            make_classifier("dt").fit(X, y)
        grown = profile.flow_totals()["h2d_bytes"] - before
        (fit,) = trace.as_dict()["spans"]
        (h2d,) = [c for c in fit["children"] if c["name"] == "h2d:train"]
        assert h2d["meta"]["rows"] == 96
        assert h2d["meta"]["h2d_bytes"] == grown > 96 * 5 * 4

    def test_lbfgs_iterations_equal_the_losses_returned(self):
        import jax.numpy as jnp

        from learningorchestra_tpu.ml import logistic

        X, y = self._data()
        params = {
            "w": jnp.zeros((5, 2), jnp.float32),
            "b": jnp.zeros((2,), jnp.float32),
        }
        trace = tracing.Trace(name="lr")
        with tracing.activate(trace), tracing.span("phase:fit"):
            _, losses = logistic._fit(
                params,
                jnp.asarray(X),
                jnp.asarray(y),
                jnp.ones(96, jnp.float32),
                max_iter=100,
                l2=jnp.float32(0.0),
            )
        (fit,) = trace.as_dict()["spans"]
        assert fit["meta"]["lbfgs_iterations"] == len(losses)
        segments = [c for c in fit["children"] if c["name"] == "fit:segment"]
        assert sum(s["meta"]["iters"] for s in segments) == len(losses)

    def test_builder_capture_carries_the_wall_clock_anchor(
        self, tmp_path, monkeypatch
    ):
        """``utils.profiling.trace`` writes one ``lo_clock`` annotation
        with the wall clock, the harness's convention, so the job's
        spans lay over an operator's LO_TRACE_DIR capture."""
        import contextlib

        import jax

        from learningorchestra_tpu.utils import profiling

        seen = []

        class Annotation(contextlib.nullcontext):
            def __init__(self, name, **stats):
                super().__init__()
                seen.append((name, stats))

        monkeypatch.setattr(
            jax.profiler, "trace", lambda d: contextlib.nullcontext()
        )
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        before = time.time()
        with profiling.trace(str(tmp_path)):
            pass
        ((name, stats),) = seen
        assert name == "lo_clock"
        assert before <= stats["wall"] <= time.time()


class TestSampler:
    def test_sample_covers_named_threads(self):
        stop = threading.Event()

        def busy():
            while not stop.is_set():
                sum(range(1000))

        worker = threading.Thread(target=busy, name="lo-busy-worker")
        worker.start()
        try:
            stacks, samples = profile.sample_stacks(0.4, hz=97)
        finally:
            stop.set()
            worker.join()
        assert samples > 5
        assert any(
            stack.startswith("lo-busy-worker;") for stack in stacks
        ), stacks
        text = profile.folded_text(stacks)
        assert text.splitlines()[0].rsplit(" ", 1)[1].isdigit()

    def test_concurrent_requests_share_one_sampler_thread(self):
        """Bounded overhead: N concurrent /debug/profile requests must
        not spawn N sampling threads."""
        app = WebApp("prof_test")
        client_results = []
        max_samplers = []

        def hit():
            client = app.test_client()
            response = client.get("/debug/profile?seconds=0.4")
            client_results.append(
                (response.status_code, response.data.decode())
            )

        def watch():
            deadline = time.monotonic() + 2.0
            peak = 0
            while time.monotonic() < deadline:
                alive = sum(
                    1
                    for t in threading.enumerate()
                    if t.name == "lo-prof-sampler"
                )
                peak = max(peak, alive)
                time.sleep(0.01)
            max_samplers.append(peak)

        watcher = threading.Thread(target=watch)
        watcher.start()
        clients = [threading.Thread(target=hit) for _ in range(4)]
        for thread in clients:
            thread.start()
        for thread in clients:
            thread.join()
        watcher.join()
        assert all(status == 200 for status, _ in client_results)
        assert all(body for _, body in client_results)
        assert max_samplers[0] == 1  # shared, never one per request
        # and the sampler thread exits once the last window closes
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            if not any(
                t.name == "lo-prof-sampler" for t in threading.enumerate()
            ):
                break
            time.sleep(0.02)
        else:
            pytest.fail("sampler thread did not stop after last release")

    def test_counts_cleared_after_last_release(self):
        profile.sample_stacks(0.1, hz=97)
        # the delta protocol reads before release; afterwards the
        # accumulated stacks are dead weight and must not persist
        # (one folded key per Thread-N name would leak forever)
        counts, samples = profile._SAMPLER.snapshot()
        assert not counts and samples == 0

    def test_malformed_knob_is_clean_json_500(self, monkeypatch):
        monkeypatch.setenv("LO_PROF_HZ", "abc")
        response = WebApp("prof_sick").test_client().get(
            "/debug/profile?seconds=1"
        )
        assert response.status_code == 500
        assert response.get_json()["result"] == "invalid_prof_config"

    def test_disabled_profiler_answers_403(self, monkeypatch):
        monkeypatch.setenv("LO_PROF_HZ", "0")
        client = WebApp("prof_off").test_client()
        response = client.get("/debug/profile?seconds=1")
        assert response.status_code == 403
        assert response.get_json() == {"result": "profiler_disabled"}

    def test_bad_seconds_400(self):
        client = WebApp("prof_bad").test_client()
        assert client.get("/debug/profile?seconds=abc").status_code == 400
        assert client.get("/debug/profile?seconds=-1").status_code == 400

    def test_knob_validation(self, monkeypatch):
        monkeypatch.setenv("LO_PROF_HZ", "-1")
        with pytest.raises(ValueError):
            profile.prof_hz()
        monkeypatch.setenv("LO_PROF_HZ", "abc")
        with pytest.raises(ValueError):
            profile.validate_env()
        monkeypatch.setenv("LO_PROF_HZ", "19")
        monkeypatch.setenv("LO_PROF_WINDOW_S", "0")
        with pytest.raises(ValueError):
            profile.validate_env()
        monkeypatch.setenv("LO_PROF_WINDOW_S", "30")
        profile.validate_env()


class TestServeForwardSpans:
    def test_sampled_forward_trace_carries_batch_attribution(
        self, tmp_path
    ):
        import numpy as np

        from learningorchestra_tpu.ml.base import make_classifier
        from learningorchestra_tpu.ml.checkpoint import (
            checkpoint_path,
            save_model,
        )
        from learningorchestra_tpu.serve.batcher import MicroBatcher
        from learningorchestra_tpu.serve.registry import ModelRegistry

        rng = np.random.default_rng(3)
        X = rng.random((64, 4), dtype=np.float32)
        y = (X[:, 0] > 0.5).astype(np.int32)
        model = make_classifier("nb").fit(X, y)
        artifact = checkpoint_path(str(tmp_path), "serve_prof_nb")
        save_model(model, artifact)
        batcher = MicroBatcher(
            ModelRegistry(capacity=10**9),
            window_s=0.0,
            max_batch=8,
            inbox_cap=32,
            trace_every=1,  # trace EVERY forward for the assertion
        )
        try:
            requests = [
                batcher.submit(artifact, X[i : i + 1]) for i in range(3)
            ]
            for request in requests:
                assert request.wait(10)
                assert request.error is None
        finally:
            batcher.close()
        # the forward ran under its own remembered trace with
        # rows/bytes + registry hit/miss attribution
        recent = [
            t
            for t in tracing._RECENT.values()
            if t.name == f"serve:{artifact}"
        ]
        assert recent
        spans = []
        for trace in recent:
            spans.extend(trace.as_dict()["spans"])
        forwards = [s for s in spans if s["name"] == "serve:forward"]
        assert forwards
        meta = forwards[0]["meta"]
        assert meta["registry"] in ("hit", "miss")
        assert meta["rows"] >= 1
        assert meta["bytes"] > 0
        total_rows = sum(s["meta"]["rows"] for s in forwards)
        assert total_rows == 3
