"""The replicated serving fleet (docs/serving.md "Fleet"): consistent-
hash placement, residency gossip, the replica agent, the placement-aware
router, and the kill-one-replica chaos drills.

Acceptance contract: placement is deterministic and distinct-owner
(losing one replica moves only its models), the gossip turns a missed
heartbeat into routing-around within ``LO_FLEET_DOWN_S``, the router
fails over in flight with ZERO wrong-model answers (every 200 names the
model the client asked for), the per-model quota answers 429 +
Retry-After before any socket opens, and the SDK rides the router
transparently off the ``/health`` feature probe. The fast drills run
in-process against real sockets; the subprocess drill (``slow`` tier)
SIGKILLs a real replica runner behind a real router runner.
"""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from learningorchestra_tpu.core.store import InMemoryStore
from learningorchestra_tpu.ml.base import make_classifier
from learningorchestra_tpu.ml.checkpoint import (
    checkpoint_path,
    gather_model,
    write_checkpoint,
)
from learningorchestra_tpu.serve import ServePlane
from learningorchestra_tpu.serve import fleet
from learningorchestra_tpu.serve import router as fleet_router
from learningorchestra_tpu.services import model_builder
from learningorchestra_tpu.testing import faults
from learningorchestra_tpu.utils.web import ServerThread
from loadgen import http_predict_sender, run_closed_loop

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_TESTS_DIR)

_FLEET_ENV = (
    "LO_FLEET_REPLICAS",
    "LO_FLEET_RF",
    "LO_FLEET_MODEL_QPS",
    "LO_FLEET_DOWN_S",
    "LO_FLEET_REPLICA",
)


@pytest.fixture(autouse=True)
def _clean_faults_and_env(monkeypatch):
    faults.reset()
    for name in _FLEET_ENV:
        monkeypatch.delenv(name, raising=False)
    yield
    faults.reset()


@pytest.fixture()
def data(rng):
    X = rng.normal(size=(200, 6))
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(int)
    return X, y


def fit_and_checkpoint(name, X, y, models_dir):
    model = make_classifier("lr").fit(X, y)
    path = checkpoint_path(str(models_dir), name)
    write_checkpoint(gather_model(model), path)
    return model, path


# ---------------------------------------------------------------------------
# Placement


class TestPlacementRing:
    def test_owners_deterministic_across_instances(self):
        a = fleet.PlacementRing(4)
        b = fleet.PlacementRing(4)
        for name in ("alpha", "beta", "gamma", "titanic_prediction_lr"):
            assert a.owners(name, rf=2) == b.owners(name, rf=2)

    def test_owners_distinct_and_primary_stable_under_rf(self):
        ring = fleet.PlacementRing(4)
        for name in (f"model{i}" for i in range(32)):
            owners = ring.owners(name, rf=3)
            assert len(owners) == len(set(owners)) == 3
            # raising rf extends the walk, never reshuffles the primary
            assert owners[0] == ring.owners(name, rf=1)[0]
            assert owners[:2] == ring.owners(name, rf=2)

    def test_rf_clamps_to_replica_count(self):
        ring = fleet.PlacementRing(2)
        assert sorted(ring.owners("m", rf=9)) == [0, 1]
        assert len(ring.owners("m", rf=0)) == 1  # floor: one owner

    def test_single_replica_owns_everything(self):
        ring = fleet.PlacementRing(1)
        assert ring.owners("anything", rf=3) == [0]

    def test_primaries_spread_over_replicas(self):
        # 64 vnodes/replica: 200 names cannot all hash to one replica
        ring = fleet.PlacementRing(4)
        primaries = {ring.owners(f"m{i}")[0] for i in range(200)}
        assert primaries == {0, 1, 2, 3}

    def test_losing_a_replica_moves_only_its_models(self):
        before = fleet.PlacementRing(4)
        after = fleet.PlacementRing(3)
        moved = survivors = 0
        for i in range(200):
            name = f"m{i}"
            old = before.owners(name)[0]
            if old == 3:  # the removed replica's models must move
                moved += 1
            elif after.owners(name)[0] == old:
                survivors += 1
        kept_total = 200 - moved
        # consistent hashing: the overwhelming share of surviving
        # primaries stays put (modulo placement would reshuffle ~2/3)
        assert survivors / kept_total > 0.9

    def test_zero_replicas_rejected(self):
        with pytest.raises(ValueError):
            fleet.PlacementRing(0)


class TestPlacementClient:
    def test_first_contact_seeds_then_everyone_adopts(self):
        store = InMemoryStore()
        seeder = fleet.PlacementClient(store, replicas=3, rf=2)
        doc = seeder.document()
        assert doc["replicas"] == 3 and doc["rf"] == 2
        assert seeder.rev >= 0
        follower = fleet.PlacementClient(store, replicas=3, rf=2)
        assert follower.document()["rf"] == 2
        assert follower.owners("alpha") == seeder.owners("alpha")
        assert len(seeder.owners("alpha")) == 2

    def test_geometry_mismatch_refuses(self):
        store = InMemoryStore()
        fleet.PlacementClient(store, replicas=3, rf=1).document()
        wrong = fleet.PlacementClient(store, replicas=2, rf=1)
        with pytest.raises(ValueError, match="LO_FLEET_REPLICAS"):
            wrong.document()

    def test_document_is_ttl_cached(self):
        store = InMemoryStore()
        client = fleet.PlacementClient(store, replicas=2, rf=1, ttl_s=60.0)
        client.document()
        calls = {"rev": 0}
        original = store.collection_rev

        def counting(name):
            calls["rev"] += 1
            return original(name)

        store.collection_rev = counting
        for _ in range(5):
            client.document()
        assert calls["rev"] == 0  # inside the TTL: no store traffic

    def test_env_defaults_feed_the_client(self, monkeypatch):
        monkeypatch.setenv("LO_FLEET_REPLICAS", "4")
        monkeypatch.setenv("LO_FLEET_RF", "2")
        client = fleet.PlacementClient(InMemoryStore())
        doc = client.document()
        assert (doc["replicas"], doc["rf"]) == (4, 2)


class TestKnobValidation:
    def test_defaults(self):
        assert fleet.validate_env() == {
            "LO_FLEET_REPLICAS": 1,
            "LO_FLEET_RF": 1,
            "LO_FLEET_MODEL_QPS": 0.0,
            "LO_FLEET_DOWN_S": 3.0,
            "LO_FLEET_REPLICA": None,
        }

    def test_parses_configured_values(self, monkeypatch):
        monkeypatch.setenv("LO_FLEET_REPLICAS", "3")
        monkeypatch.setenv("LO_FLEET_RF", "2")
        monkeypatch.setenv("LO_FLEET_MODEL_QPS", "12.5")
        monkeypatch.setenv("LO_FLEET_DOWN_S", "0.5")
        monkeypatch.setenv("LO_FLEET_REPLICA", "2")
        config = fleet.validate_env()
        assert config["LO_FLEET_REPLICAS"] == 3
        assert config["LO_FLEET_MODEL_QPS"] == 12.5
        assert config["LO_FLEET_REPLICA"] == 2

    @pytest.mark.parametrize(
        "name,value",
        [
            ("LO_FLEET_REPLICAS", "zero"),
            ("LO_FLEET_REPLICAS", "0"),
            ("LO_FLEET_RF", "-1"),
            ("LO_FLEET_RF", "1.5"),
            ("LO_FLEET_MODEL_QPS", "-3"),
            ("LO_FLEET_MODEL_QPS", "nan"),
            ("LO_FLEET_DOWN_S", "0"),
            ("LO_FLEET_DOWN_S", "soon"),
            ("LO_FLEET_REPLICA", "-1"),
            ("LO_FLEET_REPLICA", "two"),
        ],
    )
    def test_malformed_knob_refuses(self, monkeypatch, name, value):
        monkeypatch.setenv(name, value)
        with pytest.raises(ValueError, match=name):
            fleet.validate_env()

    def test_replica_index_outside_fleet_refuses(self, monkeypatch):
        monkeypatch.setenv("LO_FLEET_REPLICAS", "2")
        monkeypatch.setenv("LO_FLEET_REPLICA", "2")
        with pytest.raises(ValueError, match="outside the fleet"):
            fleet.validate_env()


# ---------------------------------------------------------------------------
# Residency gossip


class TestGossip:
    def test_heartbeat_rows_feed_the_view(self):
        store = InMemoryStore()
        fleet.Heartbeat(store, 0, "http://127.0.0.1:5010").write(
            ["alpha"], 1024, 2
        )
        fleet.Heartbeat(store, 1, "http://127.0.0.1:5011").write(
            ["beta"], 2048, 0
        )
        view = fleet.FleetView(store, ttl_s=0.0, down_s=5.0)
        rows = view.rows()
        assert set(rows) == {0, 1}
        assert view.healthy(0) and view.healthy(1)
        assert view.target(0) == ("127.0.0.1", 5010)
        residency = view.residency()
        assert residency["1"]["models"] == ["beta"]
        assert residency["1"]["pinned_bytes"] == 2048
        assert residency["0"]["healthy"] is True

    def test_rewrite_updates_not_duplicates(self):
        store = InMemoryStore()
        beat = fleet.Heartbeat(store, 0, "http://127.0.0.1:5010")
        beat.write(["alpha"], 1, 0)
        beat.write(["alpha", "beta"], 2, 1)
        view = fleet.FleetView(store, ttl_s=0.0, down_s=5.0)
        assert len(view.rows()) == 1
        assert view.rows()[0]["models"] == ["alpha", "beta"]

    def test_stale_heartbeat_goes_unhealthy(self):
        store = InMemoryStore()
        fleet.Heartbeat(store, 0, "http://127.0.0.1:5010").write([], 0, 0)
        view = fleet.FleetView(store, ttl_s=0.0, down_s=0.15)
        assert view.healthy(0)
        time.sleep(0.2)
        assert not view.healthy(0)  # missed the down window
        assert view.residency()["0"]["healthy"] is False
        # the row (and its target) survive: stale is LAST resort, not gone
        assert view.target(0) == ("127.0.0.1", 5010)

    def test_unknown_replica_and_bad_url(self):
        store = InMemoryStore()
        fleet.Heartbeat(store, 0, "not a url").write([], 0, 0)
        view = fleet.FleetView(store, ttl_s=0.0, down_s=5.0)
        assert not view.healthy(7)
        assert view.target(7) is None
        assert view.target(0) is None  # unparseable url: no target


# ---------------------------------------------------------------------------
# The replica agent


class TestReplicaAgent:
    def _plane(self):
        return ServePlane(
            capacity=10**9, window_s=0.0, max_batch=8, inbox_cap=32
        )

    def test_rf1_partitions_models_exactly_once(self, data, tmp_path):
        X, y = data
        names = [f"agent{i}_prediction_lr" for i in range(4)]
        for name in names:
            fit_and_checkpoint(name, X, y, tmp_path)
        store = InMemoryStore()
        planes = [self._plane(), self._plane()]
        agents = [
            fleet.ReplicaAgent(
                store,
                str(tmp_path),
                planes[i],
                index=i,
                url=f"http://127.0.0.1:{5010 + i}",
                total=2,
                rf=1,
                warm=False,
            )
            for i in range(2)
        ]
        try:
            summaries = [agent.refresh() for agent in agents]
            pinned = [set(s["pinned"]) for s in summaries]
            # rf=1: every model pinned by EXACTLY one replica
            assert pinned[0] | pinned[1] == set(names)
            assert not pinned[0] & pinned[1]
            for agent, summary in zip(agents, summaries):
                assert summary["errors"] == 0
                assert set(summary["assigned"]) == set(
                    name
                    for name in names
                    if agent.placement.owners(name) == [agent.index]
                )
            view = fleet.FleetView(store, ttl_s=0.0, down_s=5.0)
            rows = view.rows()
            assert rows[0]["models"] == sorted(pinned[0])
            assert rows[0]["pinned_bytes"] > 0
        finally:
            for plane in planes:
                plane.close()

    def test_full_rf_pins_everything_and_warms_once(self, data, tmp_path):
        X, y = data
        names = [f"warm{i}_prediction_lr" for i in range(2)]
        for name in names:
            fit_and_checkpoint(name, X, y, tmp_path)
        store = InMemoryStore()
        plane = self._plane()
        agent = fleet.ReplicaAgent(
            store,
            str(tmp_path),
            plane,
            index=0,
            url="http://127.0.0.1:5010",
            total=1,
            rf=1,
            warm=True,
        )
        try:
            first = agent.refresh()
            assert sorted(first["pinned"]) == sorted(names)
            assert first["warmed"] == len(names)
            # warmup is per NEW assignment, not per tick
            assert agent.refresh()["warmed"] == 0
        finally:
            plane.close()

    def test_assignment_move_releases_the_budget(self, data, tmp_path):
        X, y = data
        names = [f"rel{i}_prediction_lr" for i in range(3)]
        for name in names:
            fit_and_checkpoint(name, X, y, tmp_path)
        store = InMemoryStore()
        plane = self._plane()
        agent = fleet.ReplicaAgent(
            store,
            str(tmp_path),
            plane,
            index=0,
            url="http://127.0.0.1:5010",
            total=1,
            rf=1,
            warm=False,
        )
        try:
            assert len(agent.refresh()["pinned"]) == 3
            full_bytes = plane.registry.stats()["bytes"]
            # the checkpoint vanishing IS an assignment move: the agent
            # must release the pin and return the bytes
            os.remove(checkpoint_path(str(tmp_path), names[0]))
            second = agent.refresh()
            assert names[0] not in second["pinned"]
            assert plane.registry.stats()["bytes"] < full_bytes
        finally:
            plane.close()

    def test_unloadable_checkpoint_keeps_gossiping(self, data, tmp_path):
        X, y = data
        fit_and_checkpoint("ok_prediction_lr", X, y, tmp_path)
        # a torn artifact: the agent must pin the good model, count the
        # error, and still write its heartbeat
        bad = checkpoint_path(str(tmp_path), "torn_prediction_lr")
        with open(bad, "wb") as handle:
            handle.write(b"not a checkpoint")
        store = InMemoryStore()
        plane = self._plane()
        agent = fleet.ReplicaAgent(
            store,
            str(tmp_path),
            plane,
            index=0,
            url="http://127.0.0.1:5010",
            total=1,
            rf=1,
            warm=False,
        )
        try:
            summary = agent.refresh()
            assert summary["pinned"] == ["ok_prediction_lr"]
            assert summary["errors"] == 1
            view = fleet.FleetView(store, ttl_s=0.0, down_s=5.0)
            assert view.rows()[0]["models"] == ["ok_prediction_lr"]
        finally:
            plane.close()

    def test_agent_requires_an_index(self, tmp_path):
        with pytest.raises(ValueError, match="replica index"):
            fleet.ReplicaAgent(InMemoryStore(), str(tmp_path), None)


# ---------------------------------------------------------------------------
# The router, over real sockets


class _InProcessFleet:
    """N model_builder replicas + their agents + the router, all on
    ephemeral ports in this process — the fast chaos topology."""

    def __init__(
        self,
        models_dir,
        replicas=2,
        rf=2,
        down_s=1.5,
        model_qps=0.0,
        timeout_s=10.0,
    ):
        self.down_s = down_s
        self.store = InMemoryStore()
        self.planes = []
        self.servers = []
        self.agents = []
        for index in range(replicas):
            plane = ServePlane(
                capacity=10**9, window_s=0.0, max_batch=16, inbox_cap=128
            )
            app = model_builder.create_app(
                self.store, models_dir=str(models_dir), serve=plane
            )
            server = ServerThread(app, "127.0.0.1", 0).start()
            agent = fleet.ReplicaAgent(
                self.store,
                str(models_dir),
                plane,
                index=index,
                url=f"http://127.0.0.1:{server.port}",
                total=replicas,
                rf=rf,
                interval_s=0.15,
                placement_ttl_s=0.05,
                warm=False,
            )
            agent.refresh()  # synchronous first pin: no bring-up race
            agent.start()
            self.planes.append(plane)
            self.servers.append(server)
            self.agents.append(agent)
        self.placement = fleet.PlacementClient(
            self.store, replicas=replicas, rf=rf, ttl_s=0.05
        )
        self.view = fleet.FleetView(self.store, ttl_s=0.1, down_s=down_s)
        self.app = fleet_router.create_app(
            self.store,
            placement=self.placement,
            view=self.view,
            quota=fleet_router.ModelQuota(model_qps),
            timeout_s=timeout_s,
        )
        self.router_server = ServerThread(self.app, "127.0.0.1", 0).start()
        self.router_target = f"127.0.0.1:{self.router_server.port}"

    @staticmethod
    def retries(model):
        return fleet_router._METRICS["retries"].value(model)

    @staticmethod
    def rejected(model):
        return fleet_router._METRICS["rejected"].value(model)

    def kill(self, index):
        """SIGKILL-equivalent for an in-process replica: server socket
        closed, agent stopped — its heartbeat row freezes in place."""
        self.agents[index].stop()
        self.servers[index].stop()

    def close(self):
        for stop in (
            [self.router_server.stop]
            + [agent.stop for agent in self.agents]
            + [server.stop for server in self.servers]
            + [plane.close for plane in self.planes]
        ):
            try:
                stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass


def body(response):
    return json.loads(response.get_data())


class TestRouter:
    def test_quota_unit_semantics(self):
        quota = fleet_router.ModelQuota(2.0)
        assert quota.take("m") is None
        assert quota.take("m") is None  # burst = one second's worth
        delay = quota.take("m")
        assert delay is not None and 0 < delay <= 0.5
        assert quota.take("other") is None  # per-model buckets
        assert fleet_router.ModelQuota(0.0).take("m") is None  # off

    def test_predict_proxies_and_residency_reads(self, data, tmp_path):
        X, y = data
        model, _ = fit_and_checkpoint("rt_prediction_lr", X, y, tmp_path)
        flt = _InProcessFleet(tmp_path, replicas=2, rf=2)
        try:
            client = flt.app.test_client()
            rows = X[:5].astype(np.float32)
            response = client.post(
                "/models/rt_prediction_lr/predict",
                json={"rows": rows.tolist()},
            )
            assert response.status_code == 200
            result = body(response)["result"]
            assert result["model"] == "rt_prediction_lr"
            np.testing.assert_array_equal(
                np.array(result["predictions"]), model.predict(rows)
            )

            health = body(client.get("/health"))
            assert health["fleet_router"] is True
            assert health["replicas"] == 2

            picture = body(client.get("/models/rt_prediction_lr"))
            fleet_info = picture["result"]["fleet"]
            assert sorted(fleet_info["owners"]) == [0, 1]  # rf=2 of 2
            assert fleet_info["rf"] == 2
            assert fleet_info["placement_rev"] >= 0
            replicas = fleet_info["replicas"]
            assert set(replicas) == {"0", "1"}
            for row in replicas.values():
                assert row["healthy"] is True
                assert "rt_prediction_lr" in row["models"]

            # an unknown model relays the owner's 404 untouched
            response = client.post(
                "/models/never_built/predict", json={"rows": [[1.0] * 6]}
            )
            assert response.status_code == 404
            assert body(response) == {"result": "file_not_found"}
        finally:
            flt.close()

    def test_quota_answers_429_with_retry_after(self, data, tmp_path):
        X, y = data
        fit_and_checkpoint("q_prediction_lr", X, y, tmp_path)
        flt = _InProcessFleet(tmp_path, replicas=1, rf=1, model_qps=1.0)
        try:
            client = flt.app.test_client()
            rows = X[:2].tolist()
            rejected = flt.rejected("q_prediction_lr")
            first = client.post(
                "/models/q_prediction_lr/predict", json={"rows": rows}
            )
            assert first.status_code == 200
            second = client.post(
                "/models/q_prediction_lr/predict", json={"rows": rows}
            )
            assert second.status_code == 429
            payload = body(second)
            assert payload["result"] == "quota_exceeded"
            retry_after = float(second.headers["Retry-After"])
            assert 0 < retry_after <= 1.0
            assert payload["retry_after_s"] == pytest.approx(
                retry_after, abs=1e-9
            )
            assert flt.rejected("q_prediction_lr") == rejected + 1
        finally:
            flt.close()

    def test_route_fault_answers_clean_503(self, data, tmp_path):
        X, y = data
        fit_and_checkpoint("flt_prediction_lr", X, y, tmp_path)
        flt = _InProcessFleet(tmp_path, replicas=1, rf=1)
        try:
            client = flt.app.test_client()
            faults.install("serve.route", "error@1")
            response = client.post(
                "/models/flt_prediction_lr/predict",
                json={"rows": X[:2].tolist()},
            )
            assert response.status_code == 503
            assert body(response) == {
                "result": "routing_fault",
                "model": "flt_prediction_lr",
            }
            # budget spent: the very next request routes normally
            response = client.post(
                "/models/flt_prediction_lr/predict",
                json={"rows": X[:2].tolist()},
            )
            assert response.status_code == 200
        finally:
            flt.close()

    def test_no_heartbeats_means_503_no_replicas(self):
        store = InMemoryStore()
        app = fleet_router.create_app(
            store,
            placement=fleet.PlacementClient(store, replicas=2, rf=1),
            view=fleet.FleetView(store, ttl_s=0.0, down_s=1.0),
        )
        response = app.test_client().post(
            "/models/ghost/predict", json={"rows": [[1.0]]}
        )
        assert response.status_code == 503
        assert body(response) == {"result": "no_replicas", "model": "ghost"}


# ---------------------------------------------------------------------------
# SDK transparency


class TestSdkRouter:
    @pytest.fixture(autouse=True)
    def _fresh_probe_cache(self):
        from learningorchestra_tpu import client as sdk

        sdk.Model._router_probe_cache.clear()
        yield
        sdk.Model._router_probe_cache.clear()

    def test_predict_rides_the_router(self, data, tmp_path):
        from learningorchestra_tpu import client as sdk

        X, y = data
        model, _ = fit_and_checkpoint("sdk_prediction_lr", X, y, tmp_path)
        flt = _InProcessFleet(tmp_path, replicas=2, rf=2)
        try:
            sdk.Context(flt.router_target)
            wrapper = sdk.Model()
            assert wrapper._router_base() == f"http://{flt.router_target}"
            rows = X[:3].astype(np.float32)
            answer = wrapper.predict(
                "sdk_prediction_lr", rows.tolist(), pretty_response=False
            )
            result = answer["result"]
            assert result["model"] == "sdk_prediction_lr"
            np.testing.assert_array_equal(
                np.array(result["predictions"]), model.predict(rows)
            )
        finally:
            flt.close()

    def test_non_router_base_probes_none_once(self, data, tmp_path):
        from learningorchestra_tpu import client as sdk

        X, y = data
        fit_and_checkpoint("direct_prediction_lr", X, y, tmp_path)
        plane = ServePlane(
            capacity=10**9, window_s=0.0, max_batch=8, inbox_cap=32
        )
        app = model_builder.create_app(
            InMemoryStore(), models_dir=str(tmp_path), serve=plane
        )
        server = ServerThread(app, "127.0.0.1", 0).start()
        try:
            # a direct model_builder /health has no fleet_router field
            sdk.Context(f"127.0.0.1:{server.port}")
            wrapper = sdk.Model()
            assert wrapper._router_base() is None
            # ... and the verdict is cached: one probe per base URL
            assert sdk.Model._router_probe_cache == {
                f"http://127.0.0.1:{server.port}": False
            }
            assert wrapper._router_base() is None
        finally:
            server.stop()
            plane.close()


# ---------------------------------------------------------------------------
# The kill-one-replica chaos drills


class TestKillOneReplicaDrill:
    def _drive(self, flt, model, rows, responses, clients=4, requests=10):
        send, factory = http_predict_sender(
            [flt.router_target],
            model,
            rows,
            timeout_s=10.0,
            on_response=lambda status, payload: responses.append(
                (status, payload)
            ),
        )
        return run_closed_loop(
            send,
            clients=clients,
            requests_per_client=requests,
            rows_per_request=len(rows),
            session_factory=factory,
        )

    def test_failover_under_load_and_recovery(self, data, tmp_path):
        """The headline fast drill (docs/serving.md "Fleet"): kill the
        model's PRIMARY owner under closed-loop load. Every request
        still answers 200 for the right model (`lo_router_retries_total`
        proves failover did it), and once the dead replica misses its
        down window the fleet recovers: fresh requests route straight
        to the survivor, zero new retries."""
        X, y = data
        names = ["drill_a_prediction_lr", "drill_b_prediction_lr"]
        for name in names:
            fit_and_checkpoint(name, X, y, tmp_path)
        flt = _InProcessFleet(tmp_path, replicas=2, rf=2, down_s=1.5)
        try:
            model = names[0]
            primary = flt.placement.owners(model)[0]
            rows = X[:4].tolist()
            responses = []

            baseline = flt.retries(model)
            self._drive(flt, model, rows, responses)
            # a healthy fleet never fails over
            assert flt.retries(model) == baseline
            assert all(status == 200 for status, _ in responses)

            flt.kill(primary)
            # inside the down window the dead primary still orders
            # first (its frozen heartbeat looks fresh): every request
            # must fail over to the surviving owner, invisibly
            self._drive(flt, model, rows, responses)
            after_kill = flt.retries(model)
            assert after_kill > baseline
            assert len(responses) == 80
            assert all(status == 200 for status, _ in responses)
            # ZERO wrong-model answers: every 200 names the asked model
            assert all(
                payload["result"]["model"] == model
                for _, payload in responses
            )

            # recovery: one stale down window + a view TTL later the
            # router orders the survivor first — no more retries
            time.sleep(flt.down_s + 0.3)
            responses.clear()
            self._drive(flt, model, rows, responses)
            assert flt.retries(model) == after_kill
            assert all(status == 200 for status, _ in responses)
        finally:
            flt.close()

    def test_in_flight_failover_with_route_delay(self, data, tmp_path):
        """The `serve.route` delay fault holds one routing decision
        open while the primary dies under it — the request must still
        answer 200 from the survivor."""
        X, y = data
        fit_and_checkpoint("inflight_prediction_lr", X, y, tmp_path)
        flt = _InProcessFleet(tmp_path, replicas=2, rf=2, down_s=5.0)
        try:
            model = "inflight_prediction_lr"
            primary = flt.placement.owners(model)[0]
            baseline = flt.retries(model)
            faults.install("serve.route", "delay:0.4@1")
            outcome = {}

            def one_request():
                request = urllib.request.Request(
                    f"http://{flt.router_target}/models/{model}/predict",
                    data=json.dumps({"rows": X[:2].tolist()}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(request, timeout=10) as resp:
                    outcome["status"] = resp.status
                    outcome["body"] = json.loads(resp.read())

            thread = threading.Thread(target=one_request)
            thread.start()
            time.sleep(0.15)  # the request is parked inside the delay
            flt.kill(primary)
            thread.join(timeout=15)
            assert not thread.is_alive()
            assert outcome["status"] == 200
            assert outcome["body"]["result"]["model"] == model
            assert flt.retries(model) >= baseline + 1
        finally:
            flt.close()


# ---------------------------------------------------------------------------
# The subprocess drill: real runners, real SIGKILL (slow tier)


class _Proc:
    """One subprocess with a parsed boot line and a drained stdout."""

    def __init__(self, args, env, boot_pattern, timeout_s=180):
        self.process = subprocess.Popen(
            args,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=_REPO_ROOT,
        )
        self.boot_lines: list[str] = []
        deadline = time.monotonic() + timeout_s
        pattern = re.compile(boot_pattern)
        self.port = None
        while time.monotonic() < deadline:
            line = self.process.stdout.readline()
            if not line:
                raise AssertionError(
                    "subprocess died during bring-up:\n"
                    + "".join(self.boot_lines)
                )
            self.boot_lines.append(line)
            match = pattern.search(line)
            if match:
                self.port = int(match.group(1))
                break
        if self.port is None:
            self.terminate()
            raise AssertionError(
                "subprocess never served:\n" + "".join(self.boot_lines)
            )
        threading.Thread(
            target=lambda: all(True for _ in self.process.stdout),
            daemon=True,
        ).start()

    def kill9(self):
        os.kill(self.process.pid, signal.SIGKILL)
        self.process.wait(timeout=30)

    def terminate(self):
        if self.process.poll() is None:
            self.process.terminate()
            try:
                self.process.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.process.kill()


def _fleet_child_env(extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    for stale in (
        "LO_DATA_DIR",
        "LO_REPLICATE",
        "LO_PEERS",
        "LO_ARBITERS",
        "LO_PRIMARY_URL",
        "LO_NODE_ID",
        "LO_FLEET_REPLICA",
        "LO_SERVICE",
        "LO_PORT",
    ):
        env.pop(stale, None)
    env.update(extra)
    return env


@pytest.mark.slow
@pytest.mark.integration
def test_subprocess_drill_sigkill_one_replica(tmp_path, rng):
    """The production wiring end to end: a store subprocess, two
    replica runners (their agents pinning by placement), a router
    runner — then SIGKILL one replica mid-deployment and assert the
    router keeps answering 200 for the right model, with
    `lo_router_retries_total` > 0 scraped off the router's /metrics."""
    X = rng.normal(size=(200, 6))
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(int)
    models_dir = tmp_path / "models"
    models_dir.mkdir()
    names = ["sub_a_prediction_lr", "sub_b_prediction_lr"]
    for name in names:
        fit_and_checkpoint(name, X, y, models_dir)

    store_proc = _Proc(
        [sys.executable, "-m", "learningorchestra_tpu.core.store_service"],
        _fleet_child_env({"LO_STORE_PORT": "0"}),
        r"store server on [^:]+:(\d+)",
        timeout_s=60,
    )
    replicas: list = []
    router_proc = None
    try:
        store_url = f"http://127.0.0.1:{store_proc.port}"
        shared = {
            "LO_HOST": "127.0.0.1",
            "LO_PORT": "0",
            "LO_STORE_URL": store_url,
            "LO_MODELS_DIR": str(models_dir),
            "LO_FLEET_REPLICAS": "2",
            "LO_FLEET_RF": "2",
            "LO_FLEET_DOWN_S": "2.0",
        }
        for index in range(2):
            replicas.append(
                _Proc(
                    [
                        sys.executable,
                        "-m",
                        "learningorchestra_tpu.services.runner",
                    ],
                    _fleet_child_env(
                        {
                            **shared,
                            "LO_SERVICE": "model_builder",
                            "LO_FLEET_REPLICA": str(index),
                        }
                    ),
                    r"service model_builder on [\w.\-]+:(\d+)",
                )
            )
        router_proc = _Proc(
            [sys.executable, "-m", "learningorchestra_tpu.services.runner"],
            _fleet_child_env({**shared, "LO_SERVICE": "router"}),
            r"service router on [\w.\-]+:(\d+)",
        )
        router = f"http://127.0.0.1:{router_proc.port}"

        def predict(model, timeout=30):
            request = urllib.request.Request(
                f"{router}/models/{model}/predict",
                data=json.dumps({"rows": X[:4].tolist()}).encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(request, timeout=timeout) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as error:
                return error.code, json.loads(error.read())

        # wait until BOTH replicas gossip both models (rf=2 = full
        # replication), so the kill provably leaves a serving copy
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            with urllib.request.urlopen(
                f"{router}/models/{names[0]}", timeout=10
            ) as response:
                picture = json.loads(response.read())["result"]["fleet"]
            rows = picture["replicas"]
            if len(rows) == 2 and all(
                set(names) <= set(row["models"]) for row in rows.values()
            ):
                break
            time.sleep(0.25)
        else:
            raise AssertionError(f"replicas never pinned: {picture}")

        status, payload = predict(names[0])
        assert status == 200
        assert payload["result"]["model"] == names[0]

        replicas[0].kill9()
        # inside the down window: every answer must still be a 200 for
        # the right model — failover, not error
        for _ in range(10):
            status, payload = predict(names[0])
            assert status == 200, payload
            assert payload["result"]["model"] == names[0]

        with urllib.request.urlopen(f"{router}/metrics", timeout=10) as r:
            metrics_text = r.read().decode()
        match = re.search(
            r'lo_router_retries_total\{model="%s"\} (\d+)' % names[0],
            metrics_text,
        )
        assert match and int(match.group(1)) > 0, metrics_text

        # after the down window the router marks the corpse unhealthy
        time.sleep(2.5)
        with urllib.request.urlopen(
            f"{router}/models/{names[0]}", timeout=10
        ) as response:
            picture = json.loads(response.read())["result"]["fleet"]
        health = {
            index: row["healthy"]
            for index, row in picture["replicas"].items()
        }
        assert health["0"] is False and health["1"] is True
        status, payload = predict(names[1])
        assert status == 200
        assert payload["result"]["model"] == names[1]
    finally:
        if router_proc is not None:
            router_proc.terminate()
        for proc in replicas:
            proc.terminate()
        store_proc.terminate()
