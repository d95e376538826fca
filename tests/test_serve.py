"""Online serving: device-resident registry, micro-batching, REST lane.

Covers the acceptance contract of the serving subsystem
(docs/serving.md): predictions from the registry equal the in-memory
``FittedModel.predict`` bit-for-bit, a rebuild is never served stale,
evictions stay within ``LO_SERVE_BYTES``, a concurrent burst coalesces
into multi-request dispatches, and every failure mode of the REST lane
answers a clean JSON error — never a traceback.
"""

import json
import threading
import time

import numpy as np
import pytest

from learningorchestra_tpu.core.store import InMemoryStore
from learningorchestra_tpu.ml.base import make_classifier
from learningorchestra_tpu.ml.checkpoint import (
    checkpoint_path,
    gather_model,
    write_checkpoint,
)
from learningorchestra_tpu.sched import QueueFullError
from learningorchestra_tpu.serve import (
    MicroBatcher,
    ModelNotFoundError,
    ModelRegistry,
    ServePlane,
)
from learningorchestra_tpu.serve.registry import _model_nbytes
from learningorchestra_tpu.services import model_builder


def body(response):
    return json.loads(response.get_data())


@pytest.fixture()
def data(rng):
    X = rng.normal(size=(200, 6))
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(int)
    return X, y


def fit_and_checkpoint(name, X, y, models_dir, kind="lr"):
    X_fit = np.abs(X) if kind == "nb" else X
    model = make_classifier(kind).fit(X_fit, y)
    path = checkpoint_path(str(models_dir), name)
    write_checkpoint(gather_model(model), path)
    return model, path, X_fit


class _FakeModel:
    def predict_both(self, X):
        return (
            np.zeros(len(X), np.int64),
            np.zeros((len(X), 2), np.float32),
        )


class _GateRegistry:
    """Registry stand-in whose get() blocks until the gate opens — the
    deterministic way to hold a forward in flight while the inbox
    fills."""

    def __init__(self):
        self.gate = threading.Event()
        self.calls = 0

    def get(self, path):
        self.calls += 1
        if not self.gate.wait(timeout=10):
            raise TimeoutError("gate never opened")
        return _FakeModel()


def wait_until(predicate, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.002)
    return False


class TestRegistry:
    def test_pin_and_hit(self, data, tmp_path):
        X, y = data
        _, path, _ = fit_and_checkpoint("r_prediction_lr", X, y, tmp_path)
        registry = ModelRegistry(capacity=10**9)
        first = registry.get(path)
        second = registry.get(path)
        assert first is second  # pinned, not reloaded
        stats = registry.stats()
        assert stats["hits"] == 1 and stats["misses"] == 1
        assert stats["models"] == 1 and stats["bytes"] > 0

    def test_missing_artifact_raises(self, tmp_path):
        registry = ModelRegistry(capacity=10**9)
        with pytest.raises(ModelNotFoundError):
            registry.get(str(tmp_path / "never_built.model"))

    def test_deleted_artifact_drops_entry(self, data, tmp_path):
        X, y = data
        _, path, _ = fit_and_checkpoint("d_prediction_lr", X, y, tmp_path)
        registry = ModelRegistry(capacity=10**9)
        registry.get(path)
        import os

        os.remove(path)
        with pytest.raises(ModelNotFoundError):
            registry.get(path)
        stats = registry.stats()
        assert stats["models"] == 0 and stats["bytes"] == 0

    def test_deleted_mid_load_maps_to_not_found(self, data, tmp_path, monkeypatch):
        """An artifact that vanishes between the rev stat and the open
        is the same late-404 as a failed stat — never a 500."""
        X, y = data
        _, path, _ = fit_and_checkpoint("mid_prediction_lr", X, y, tmp_path)
        registry = ModelRegistry(capacity=10**9)

        def vanished(self, p):
            raise FileNotFoundError(p)

        monkeypatch.setattr(ModelRegistry, "_load", vanished)
        with pytest.raises(ModelNotFoundError):
            registry.get(path)

    def test_lru_eviction_stays_within_budget(self, data, tmp_path):
        X, y = data
        paths = []
        for index in range(3):
            _, path, _ = fit_and_checkpoint(
                f"e{index}_prediction_lr", X, y, tmp_path
            )
            paths.append(path)
        probe = ModelRegistry(capacity=10**9)
        sizes = [_model_nbytes(probe.get(path)) for path in paths]
        # room for exactly two models: loading the third evicts the LRU
        capacity = sizes[0] + sizes[1]
        registry = ModelRegistry(capacity=capacity)
        for path in paths:
            registry.get(path)
            assert registry.stats()["bytes"] <= capacity
        stats = registry.stats()
        assert stats["evictions"] >= 1 and stats["models"] == 2
        # the evicted (least recently used) model misses again
        misses_before = registry.stats()["misses"]
        registry.get(paths[0])
        assert registry.stats()["misses"] == misses_before + 1

    def test_zero_budget_host_fallback(self, data, tmp_path):
        X, y = data
        model, path, X_fit = fit_and_checkpoint(
            "hf_prediction_lr", X, y, tmp_path
        )
        registry = ModelRegistry(capacity=0)
        first = registry.get(path)
        second = registry.get(path)
        assert first is not second  # nothing pinned
        stats = registry.stats()
        assert stats["bytes"] == 0 and stats["models"] == 0
        assert stats["misses"] == 2 and stats["hits"] == 0
        np.testing.assert_array_equal(
            first.predict(X_fit.astype(np.float32)),
            model.predict(X_fit.astype(np.float32)),
        )


class TestCheckpointRoundTrip:
    """write_checkpoint → registry load → predict equals the in-memory
    FittedModel.predict bit-for-bit, per model kind — including after a
    simulated rebuild bumps the artifact (never stale HBM)."""

    @pytest.mark.parametrize("kind", ["lr", "nb", "dt", "rf", "gb"])
    def test_registry_matches_in_memory_model(self, kind, data, tmp_path):
        X, y = data
        model, path, X_fit = fit_and_checkpoint(
            f"rt_{kind}_prediction", X, y, tmp_path, kind=kind
        )
        registry = ModelRegistry(capacity=10**9)
        served = registry.get(path)
        rows = X_fit.astype(np.float32)
        expect_labels, expect_probs = model.predict_both(rows)
        got_labels, got_probs = served.predict_both(rows)
        np.testing.assert_array_equal(got_labels, expect_labels)
        np.testing.assert_array_equal(got_probs, expect_probs)

        # simulated rebuild: flipped labels overwrite the SAME artifact
        rebuilt = make_classifier(kind).fit(X_fit, 1 - y)
        write_checkpoint(gather_model(rebuilt), path)
        served = registry.get(path)
        flip_labels, flip_probs = rebuilt.predict_both(rows)
        np.testing.assert_array_equal(served.predict_both(rows)[0], flip_labels)
        np.testing.assert_array_equal(served.predict_both(rows)[1], flip_probs)
        assert registry.stats()["invalidations"] == 1


class TestMicroBatcher:
    def test_burst_coalesces_into_batched_dispatches(self, data, tmp_path):
        """The acceptance burst: >= 64 concurrent single-row requests
        serve in far fewer dispatches (mean batch size > 1), every
        answer equal to the in-memory model's."""
        X, y = data
        model, path, _ = fit_and_checkpoint(
            "b_prediction_lr", X, y, tmp_path
        )
        plane = ServePlane(
            capacity=10**9, window_s=0.005, max_batch=32, inbox_cap=256
        )
        try:
            rows = X.astype(np.float32)
            requests = [None] * 64
            barrier = threading.Barrier(64)

            def submit(index):
                barrier.wait()
                requests[index] = plane.submit(path, rows[index : index + 1])

            threads = [
                threading.Thread(target=submit, args=(i,)) for i in range(64)
            ]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join()
            for request in requests:
                assert request.wait(30) and request.error is None
            expected = model.predict(rows[:64])
            got = np.array([requests[i].labels[0] for i in range(64)])
            np.testing.assert_array_equal(got, expected)
            stats = plane.batcher.stats()
            assert stats["batched_requests"] == 64
            assert stats["batches"] < 64  # >= 1 multi-request dispatch
            assert stats["mean_batch_size"] > 1
        finally:
            plane.close()

    def test_forward_seconds_observed_once_per_dispatch(self, data, tmp_path):
        """``lo_serve_forward_seconds`` is the lane's service time,
        registry lookup to results on the host, beside the queue wait:
        one observation per forward that ran, none for one that failed."""
        from learningorchestra_tpu.telemetry import global_registry

        def family(name):
            text = global_registry().render()
            assert f"# TYPE {name} histogram" in text
            values = {}
            for line in text.splitlines():
                if line.startswith((f"{name}_count", f"{name}_sum")):
                    key, value = line.split()
                    values[key.rsplit("_", 1)[1]] = float(value)
            return values

        X, y = data
        _, path, _ = fit_and_checkpoint("f_prediction_lr", X, y, tmp_path)
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=8, inbox_cap=32
        )
        try:
            rows = X.astype(np.float32)
            first = plane.submit(path, rows[:1])
            assert first.wait(30) and first.error is None
            before = family("lo_serve_forward_seconds")
            waits_before = family("lo_serve_queue_wait_seconds")
            batches_before = plane.batcher.stats()["batches"]
            for index in range(3):
                request = plane.submit(path, rows[index : index + 1])
                assert request.wait(30) and request.error is None
            missing = plane.submit(str(tmp_path / "nowhere"), rows[:1])
            assert missing.wait(30) and missing.error is not None
            after = family("lo_serve_forward_seconds")
            ran = plane.batcher.stats()["batches"] - batches_before
            assert ran >= 1
            assert after["count"] - before["count"] == ran
            assert after["sum"] > before["sum"]
            # the failed forward was dispatched (it waited) but not served
            waits = family("lo_serve_queue_wait_seconds")
            assert waits["count"] - waits_before["count"] == 4
        finally:
            plane.close()

    def test_width_mismatch_fails_alone(self, data, tmp_path):
        X, y = data
        model, path, _ = fit_and_checkpoint(
            "w_prediction_lr", X, y, tmp_path
        )
        plane = ServePlane(
            capacity=10**9, window_s=0.05, max_batch=8, inbox_cap=32
        )
        try:
            good = plane.submit(path, X[:2].astype(np.float32))
            bad = plane.submit(path, np.zeros((1, 2), np.float32))
            assert good.wait(30) and bad.wait(30)
            assert good.error is None
            np.testing.assert_array_equal(
                good.labels, model.predict(X[:2].astype(np.float32))
            )
            assert bad.error is not None  # wrong width fails only itself
        finally:
            plane.close()

    def test_bounded_inbox_rejects_with_retry_after(self):
        registry = _GateRegistry()
        batcher = MicroBatcher(
            registry, window_s=0.0, max_batch=4, inbox_cap=1
        )
        try:
            first = batcher.submit("m", np.zeros((1, 3), np.float32))
            # worker picked first up and is now blocked in the forward
            assert wait_until(lambda: registry.calls == 1)
            second = batcher.submit("m", np.zeros((1, 3), np.float32))
            with pytest.raises(QueueFullError) as excinfo:
                batcher.submit("m", np.zeros((1, 3), np.float32))
            assert excinfo.value.job_class == "serve"
            assert excinfo.value.retry_after_s >= 1
            assert batcher.stats()["rejected"] == 1
            registry.gate.set()
            assert first.wait(10) and second.wait(10)
            assert first.error is None and second.error is None
        finally:
            registry.gate.set()
            batcher.close()

    def test_window_zero_drains_backlog_into_one_batch(self):
        registry = _GateRegistry()
        batcher = MicroBatcher(
            registry, window_s=0.0, max_batch=16, inbox_cap=32
        )
        try:
            first = batcher.submit("m", np.zeros((1, 3), np.float32))
            assert wait_until(lambda: registry.calls == 1)
            backlog = [
                batcher.submit("m", np.zeros((1, 3), np.float32))
                for _ in range(5)
            ]
            registry.gate.set()
            for request in [first] + backlog:
                assert request.wait(10) and request.error is None
            # the 5 queued while the first forward ran became ONE batch
            assert batcher.batches == 2
        finally:
            registry.gate.set()
            batcher.close()

    def test_submit_rejects_malformed_rows_and_lane_survives(
        self, data, tmp_path
    ):
        """Malformed rows fail on the CALLER's thread (ValueError), so
        a bad library submission can never kill the worker loop and
        wedge the lane for every later request."""
        X, y = data
        _, path, _ = fit_and_checkpoint("mv_prediction_lr", X, y, tmp_path)
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=4, inbox_cap=8
        )
        try:
            with pytest.raises(ValueError):
                plane.submit(path, np.zeros(3, np.float32))  # 1-D
            with pytest.raises(ValueError):
                plane.submit(path, np.zeros((0, 3), np.float32))  # empty
            good = plane.submit(path, X[:1].astype(np.float32))
            assert good.wait(30) and good.error is None
        finally:
            plane.close()

    def test_abandoned_requests_never_dispatch(self):
        """A timed-out (503) client's request is dropped at dispatch —
        the registry is never consulted and no forward runs for it."""
        registry = _GateRegistry()
        batcher = MicroBatcher(
            registry, window_s=0.0, max_batch=4, inbox_cap=8
        )
        try:
            first = batcher.submit("m", np.zeros((1, 3), np.float32))
            assert wait_until(lambda: registry.calls == 1)
            dead = batcher.submit("m", np.zeros((1, 3), np.float32))
            dead.abandon()  # what the route does after answering 503
            registry.gate.set()
            live = batcher.submit("m", np.zeros((1, 3), np.float32))
            assert first.wait(10) and dead.wait(10) and live.wait(10)
            assert first.error is None and live.error is None
            assert dead.labels is None and dead.error is not None
            # batches: [first], [live] — the abandoned one cost nothing
            assert registry.calls == 2
        finally:
            registry.gate.set()
            batcher.close()

    def test_multi_row_requests_bound_collection(self):
        """Accumulated rows >= max_batch stop the collection early, so
        one dispatch never drains an unbounded row count."""
        registry = _GateRegistry()
        batcher = MicroBatcher(
            registry, window_s=0.05, max_batch=4, inbox_cap=16
        )
        try:
            first = batcher.submit("m", np.zeros((1, 3), np.float32))
            assert wait_until(lambda: registry.calls == 1)
            # 4 rows reach the row budget exactly; the fifth request
            # must land in a SEPARATE dispatch
            wide = batcher.submit("m", np.zeros((4, 3), np.float32))
            tail = batcher.submit("m", np.zeros((1, 3), np.float32))
            registry.gate.set()
            for request in (first, wide, tail):
                assert request.wait(10) and request.error is None
            assert batcher.batches == 3
        finally:
            registry.gate.set()
            batcher.close()

    def test_close_fails_pending(self):
        registry = _GateRegistry()
        batcher = MicroBatcher(
            registry, window_s=0.0, max_batch=4, inbox_cap=8
        )
        first = batcher.submit("m", np.zeros((1, 3), np.float32))
        assert wait_until(lambda: registry.calls == 1)
        stuck = batcher.submit("m", np.zeros((1, 3), np.float32))
        registry.gate.set()
        batcher.close()
        assert first.wait(10)
        assert stuck.wait(10)  # answered: completed or failed, never hung
        with pytest.raises(RuntimeError):
            batcher.submit("m", np.zeros((1, 3), np.float32))


class TestServeRoutes:
    def make_app(self, models_dir, plane):
        return model_builder.create_app(
            InMemoryStore(), models_dir=str(models_dir), serve=plane
        )

    def test_predict_matches_in_memory_model(self, data, tmp_path):
        X, y = data
        model, _, _ = fit_and_checkpoint(
            "svc_prediction_lr", X, y, tmp_path
        )
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=8, inbox_cap=32
        )
        try:
            client = self.make_app(tmp_path, plane).test_client()
            rows = X[:5].astype(np.float32)
            response = client.post(
                "/models/svc_prediction_lr/predict",
                json={"rows": rows.tolist()},
            )
            assert response.status_code == 200
            result = body(response)["result"]
            assert result["model"] == "svc_prediction_lr"
            np.testing.assert_array_equal(
                np.array(result["predictions"]), model.predict(rows)
            )
            probs = np.array(result["probabilities"], np.float32)
            np.testing.assert_array_equal(probs, model.predict_proba(rows))
            # a single flat row is one request
            response = client.post(
                "/models/svc_prediction_lr/predict",
                json={"rows": rows[0].tolist()},
            )
            assert response.status_code == 200
            assert len(body(response)["result"]["predictions"]) == 1
        finally:
            plane.close()

    def test_unknown_model_404_json(self, tmp_path):
        plane = ServePlane(capacity=0, window_s=0.0, max_batch=2, inbox_cap=4)
        try:
            client = self.make_app(tmp_path, plane).test_client()
            response = client.post(
                "/models/never_built/predict", json={"rows": [[1.0, 2.0]]}
            )
            assert response.status_code == 404
            assert body(response) == {"result": "file_not_found"}
            # traversal-looking names are rejected the same clean way
            response = client.post(
                "/models/..%2Fetc/predict", json={"rows": [[1.0]]}
            )
            assert response.status_code == 404
        finally:
            plane.close()

    def test_malformed_rows_406_json(self, data, tmp_path):
        X, y = data
        fit_and_checkpoint("mf_prediction_lr", X, y, tmp_path)
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=4, inbox_cap=8
        )
        try:
            client = self.make_app(tmp_path, plane).test_client()
            url = "/models/mf_prediction_lr/predict"
            assert client.post(url, json={"nope": 1}).status_code == 406
            assert client.post(url, json={"rows": []}).status_code == 406
            ragged = client.post(url, json={"rows": [[1, 2], [3]]})
            assert ragged.status_code == 406
            assert body(ragged) == {"result": "invalid_rows"}
            strings = client.post(url, json={"rows": [["a", "b"]]})
            assert strings.status_code == 406
            # JSON null converts to NaN without raising — must still 406,
            # never 200 with NaN "probabilities"
            nulls = client.post(
                url, json={"rows": [[1.0, None, 2.0, 3.0, 4.0, 5.0]]}
            )
            assert nulls.status_code == 406
            assert body(nulls) == {"result": "invalid_rows"}
        finally:
            plane.close()

    def test_forward_failure_is_clean_json_500(self, data, tmp_path):
        X, y = data
        fit_and_checkpoint("ff_prediction_lr", X, y, tmp_path)
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=4, inbox_cap=8
        )
        try:
            client = self.make_app(tmp_path, plane).test_client()
            # wrong feature width survives np.asarray but fails the
            # forward — the route must answer JSON, not a traceback
            response = client.post(
                "/models/ff_prediction_lr/predict",
                json={"rows": [[1.0, 2.0]]},
            )
            assert response.status_code == 500
            message = body(response)["result"]
            assert message.startswith("prediction_failed:")
            assert "Traceback" not in message
        finally:
            plane.close()

    def test_oversized_request_413(self, data, tmp_path, monkeypatch):
        X, y = data
        fit_and_checkpoint("big_prediction_lr", X, y, tmp_path)
        monkeypatch.setenv("LO_SERVE_MAX_ROWS", "8")
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=4, inbox_cap=8
        )
        try:
            client = self.make_app(tmp_path, plane).test_client()
            url = "/models/big_prediction_lr/predict"
            over = client.post(url, json={"rows": X[:9].tolist()})
            assert over.status_code == 413
            assert body(over) == {"result": "too_many_rows"}
            at_cap = client.post(url, json={"rows": X[:8].tolist()})
            assert at_cap.status_code == 200
        finally:
            plane.close()

    def test_inbox_full_429_with_retry_after(self, data, tmp_path):
        X, y = data
        fit_and_checkpoint("full_prediction_lr", X, y, tmp_path)
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=2, inbox_cap=1
        )
        gate = _GateRegistry()
        plane.batcher.registry = gate  # hold the forward in flight
        try:
            client = self.make_app(tmp_path, plane).test_client()
            url = "/models/full_prediction_lr/predict"
            payload = {"rows": X[:1].tolist()}
            results = []

            def blocked():
                results.append(client.post(url, json=payload).status_code)

            runner = threading.Thread(target=blocked)
            runner.start()
            assert wait_until(lambda: gate.calls == 1)
            filler = threading.Thread(target=blocked)
            filler.start()
            assert wait_until(lambda: plane.batcher.depth() == 1)
            rejected = client.post(url, json=payload)
            assert rejected.status_code == 429
            assert body(rejected)["result"] == "queue_full"
            assert body(rejected)["job_class"] == "serve"
            assert int(rejected.headers["Retry-After"]) >= 1
            gate.gate.set()
            runner.join(10)
            filler.join(10)
        finally:
            gate.gate.set()
            plane.close()

    def test_slow_forward_times_out_503(self, data, tmp_path, monkeypatch):
        X, y = data
        fit_and_checkpoint("slow_prediction_lr", X, y, tmp_path)
        monkeypatch.setenv("LO_SERVE_TIMEOUT_S", "0.05")
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=2, inbox_cap=4
        )
        gate = _GateRegistry()
        plane.batcher.registry = gate
        try:
            client = self.make_app(tmp_path, plane).test_client()
            response = client.post(
                "/models/slow_prediction_lr/predict",
                json={"rows": X[:1].tolist()},
            )
            assert response.status_code == 503
            assert body(response) == {"result": "predict_timeout"}
        finally:
            gate.gate.set()
            plane.close()

    def test_rebuild_served_fresh_through_route(self, data, tmp_path):
        X, y = data
        _, path, X_fit = fit_and_checkpoint(
            "rb_prediction_lr", X, y, tmp_path
        )
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=8, inbox_cap=16
        )
        try:
            client = self.make_app(tmp_path, plane).test_client()
            url = "/models/rb_prediction_lr/predict"
            rows = X_fit[:8].astype(np.float32)
            first = body(client.post(url, json={"rows": rows.tolist()}))
            rebuilt = make_classifier("lr").fit(X_fit, 1 - y)
            write_checkpoint(gather_model(rebuilt), path)
            second = body(client.post(url, json={"rows": rows.tolist()}))
            np.testing.assert_array_equal(
                np.array(second["result"]["predictions"]),
                rebuilt.predict(rows),
            )
            # flipped labels: the rebuild is visibly NOT the old model
            assert second["result"]["predictions"] != first["result"][
                "predictions"
            ]
            assert plane.registry.stats()["invalidations"] == 1
        finally:
            plane.close()

    def test_registry_disabled_still_serves_correctly(self, data, tmp_path):
        """LO_SERVE_BYTES=0 (capacity 0): host-memory fallback path —
        nothing pinned, predictions still exact."""
        X, y = data
        model, _, _ = fit_and_checkpoint(
            "nofb_prediction_lr", X, y, tmp_path
        )
        plane = ServePlane(capacity=0, window_s=0.0, max_batch=4, inbox_cap=8)
        try:
            client = self.make_app(tmp_path, plane).test_client()
            rows = X[:4].astype(np.float32)
            response = client.post(
                "/models/nofb_prediction_lr/predict",
                json={"rows": rows.tolist()},
            )
            assert response.status_code == 200
            np.testing.assert_array_equal(
                np.array(body(response)["result"]["predictions"]),
                model.predict(rows),
            )
            stats = plane.registry.stats()
            assert stats["bytes"] == 0 and stats["models"] == 0
        finally:
            plane.close()

    def test_listing_and_status_carry_serving_info(self, data, tmp_path):
        X, y = data
        fit_and_checkpoint("ls_prediction_lr", X, y, tmp_path)
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=4, inbox_cap=8
        )
        try:
            client = self.make_app(tmp_path, plane).test_client()
            listing = body(client.get("/models"))
            assert listing["result"] == ["ls_prediction_lr"]
            assert listing["serving"]["registry"]["models"] == 0
            info = body(client.get("/models/ls_prediction_lr"))["result"]
            assert info["serving"] == {"resident": False}
            client.post(
                "/models/ls_prediction_lr/predict",
                json={"rows": X[:1].tolist()},
            )
            info = body(client.get("/models/ls_prediction_lr"))["result"]
            assert info["serving"]["resident"] is True
            assert info["serving"]["bytes"] > 0
        finally:
            plane.close()


class TestLoadGenerator:
    def _serve_app(self, data, tmp_path, **knobs):
        X, y = data
        model, _, _ = fit_and_checkpoint(
            "lg_prediction_lr", X, y, tmp_path
        )
        plane = ServePlane(capacity=10**9, **knobs)
        app = model_builder.create_app(
            InMemoryStore(), models_dir=str(tmp_path), serve=plane
        )
        return X, plane, app

    def _run(self, X, plane, app, clients, requests_per_client):
        from loadgen import run_closed_loop

        handles = [app.test_client() for _ in range(clients)]
        row = X[:1].tolist()

        def send(index):
            response = handles[index].post(
                "/models/lg_prediction_lr/predict", json={"rows": row}
            )
            assert response.status_code == 200

        return run_closed_loop(send, clients, requests_per_client)

    def test_smoke_closed_loop(self, data, tmp_path):
        """Tier-1 smoke config: small client counts, few requests."""
        X, plane, app = self._serve_app(
            data, tmp_path, window_s=0.001, max_batch=16, inbox_cap=256
        )
        try:
            for clients in (1, 8):
                stats = self._run(X, plane, app, clients, 10)
                assert stats["requests"] == clients * 10
                assert stats["p99_ms"] >= stats["p50_ms"] > 0
                assert stats["predictions_per_s"] > 0
            assert plane.batcher.stats()["batched_requests"] == 90
        finally:
            plane.close()

    @pytest.mark.slow
    def test_concurrency_sweep_batches(self, data, tmp_path):
        """At full size: 64 concurrent closed-loop clients must achieve
        mean batch size > 1."""
        X, plane, app = self._serve_app(
            data, tmp_path, window_s=0.001, max_batch=64, inbox_cap=1024
        )
        try:
            before = plane.batcher.stats()
            stats = self._run(X, plane, app, 64, 50)
            after = plane.batcher.stats()
            batches = after["batches"] - before["batches"]
            grouped = after["batched_requests"] - before["batched_requests"]
            assert stats["requests"] == 64 * 50
            assert grouped / batches > 1  # micro-batching engaged
        finally:
            plane.close()

    def test_sessions_close_on_success_and_send_error(self):
        """Per-client sessions are handed to send and closed in
        ``finally`` — including when a send raises mid-loop (the leak
        path: a failed client used to abandon its connection)."""
        from loadgen import run_closed_loop

        class Session:
            def __init__(self, index):
                self.index = index
                self.closed = False

            def close(self):
                self.closed = True

        sessions = []

        def session_factory(index):
            session = Session(index)
            sessions.append(session)
            return session

        def send(index, session):
            assert session.index == index
            if index == 2:
                raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            run_closed_loop(
                send, 4, 3, session_factory=session_factory
            )
        assert len(sessions) == 4
        assert all(session.closed for session in sessions)

        sessions.clear()
        stats = run_closed_loop(
            lambda index, session: None,
            3,
            2,
            session_factory=session_factory,
        )
        assert stats["requests"] == 6
        assert all(session.closed for session in sessions)

    def test_session_factory_failure_aborts_barrier(self):
        """A client dying BEFORE the start barrier must abort it (no
        deadlock) and surface the root cause, not the collateral
        BrokenBarrierError the other clients see."""
        from loadgen import run_closed_loop

        opened = []

        class Session:
            def __init__(self):
                self.closed = False
                opened.append(self)

            def close(self):
                self.closed = True

        def session_factory(index):
            if index == 1:
                raise OSError("connect refused")
            return Session()

        with pytest.raises(OSError, match="connect refused"):
            run_closed_loop(
                lambda index, session: None,
                3,
                5,
                session_factory=session_factory,
            )
        assert all(session.closed for session in opened)

    def test_http_sender_parameterizes_targets(self):
        """Client i's session targets targets[i % len(targets)] — one
        target is router mode, several spread clients across replicas.
        No hardcoded single target anywhere."""
        from loadgen import (
            http_predict_sender,
        )

        targets = ["127.0.0.1:5102", "http://127.0.0.1:5103"]
        send, session_factory = http_predict_sender(
            targets, "m", [[1.0]]
        )
        assigned = [session_factory(i).target for i in range(4)]
        assert assigned == [
            "127.0.0.1:5102",
            "http://127.0.0.1:5103",
            "127.0.0.1:5102",
            "http://127.0.0.1:5103",
        ]
        with pytest.raises(ValueError, match="at least one target"):
            http_predict_sender([], "m", [[1.0]])


class TestServeConfig:
    def test_defaults(self, monkeypatch):
        from learningorchestra_tpu.serve import config

        for knob in (
            "LO_SERVE_BYTES",
            "LO_SERVE_BATCH_WINDOW_MS",
            "LO_SERVE_MAX_BATCH",
            "LO_SERVE_MAX_ROWS",
            "LO_SERVE_QUEUE_CAP",
            "LO_SERVE_TIMEOUT_S",
        ):
            monkeypatch.delenv(knob, raising=False)
        resolved = config.validate_all()
        assert resolved["serve_bytes"] == 1_000_000_000
        assert resolved["batch_window_s"] == pytest.approx(0.001)
        assert resolved["max_batch"] == 64
        assert resolved["max_rows"] == 4096
        assert resolved["queue_cap"] == 256
        assert resolved["request_timeout_s"] == 30.0

    @pytest.mark.parametrize(
        "knob,value",
        [
            ("LO_SERVE_BYTES", "lots"),
            ("LO_SERVE_BYTES", "-1"),
            ("LO_SERVE_BATCH_WINDOW_MS", "-0.5"),
            ("LO_SERVE_BATCH_WINDOW_MS", "soon"),
            ("LO_SERVE_MAX_BATCH", "0"),
            ("LO_SERVE_MAX_BATCH", "1.5"),  # count knobs never truncate
            ("LO_SERVE_MAX_ROWS", "0"),
            ("LO_SERVE_MAX_ROWS", "2.5"),
            ("LO_SERVE_QUEUE_CAP", "0"),
            ("LO_SERVE_QUEUE_CAP", "ten"),
            ("LO_SERVE_TIMEOUT_S", "0"),
        ],
    )
    def test_rejects_bad_values(self, monkeypatch, knob, value):
        from learningorchestra_tpu.serve import config

        monkeypatch.setenv(knob, value)
        with pytest.raises(ValueError):
            config.validate_all()

    def test_zero_window_and_zero_bytes_are_valid(self, monkeypatch):
        from learningorchestra_tpu.serve import config

        monkeypatch.setenv("LO_SERVE_BYTES", "0")
        monkeypatch.setenv("LO_SERVE_BATCH_WINDOW_MS", "0")
        resolved = config.validate_all()
        assert resolved["serve_bytes"] == 0
        assert resolved["batch_window_s"] == 0.0


class TestClientSdk:
    def test_predict_and_list_models_over_http(self, data, tmp_path):
        """The SDK lane: Model.predict / Model.list_models against a
        live server — no raw HTTP in user scripts (docs/serving.md)."""
        import learningorchestra_tpu.client as lo_client
        from learningorchestra_tpu.utils.web import ServerThread

        X, y = data
        model, _, _ = fit_and_checkpoint(
            "sdk_prediction_lr", X, y, tmp_path
        )
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=8, inbox_cap=32
        )
        app = model_builder.create_app(
            InMemoryStore(), models_dir=str(tmp_path), serve=plane
        )
        server = ServerThread(app, "127.0.0.1", 0).start()
        saved_port = lo_client.Model.MODEL_BUILDER_PORT
        try:
            lo_client.Model.MODEL_BUILDER_PORT = str(server.port)
            lo_client.Context("127.0.0.1")
            sdk = lo_client.Model()
            listing = sdk.list_models(pretty_response=False)
            assert listing["result"] == ["sdk_prediction_lr"]
            rows = X[:3].astype(np.float32)
            result = sdk.predict(
                "sdk_prediction_lr", rows.tolist(), pretty_response=False
            )
            np.testing.assert_array_equal(
                np.array(result["result"]["predictions"]),
                model.predict(rows),
            )
            # the reference-parity PyPI shim exposes the same surface
            from learning_orchestra_client import Model as ShimModel

            assert ShimModel is lo_client.Model
            with pytest.raises(Exception, match="file_not_found"):
                sdk.predict("ghost", [[1.0]], pretty_response=False)
        finally:
            lo_client.Model.MODEL_BUILDER_PORT = saved_port
            server.stop()
            plane.close()
