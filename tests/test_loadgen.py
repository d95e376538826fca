"""The closed-loop load generator (tests/loadgen.py): per-client
sessions must be closed on EVERY path out of the client loop — error
paths included — targets parameterize (router mode is one target,
replica mode several), and a client that dies during setup aborts the
start barrier instead of deadlocking the run.
"""

import threading

import pytest

from learningorchestra_tpu.utils.web import ServerThread, WebApp
from loadgen import HttpSession, http_predict_sender, run_closed_loop


class _TrackingSession:
    def __init__(self, index):
        self.index = index
        self.closed = False

    def close(self):
        self.closed = True


class TestRunClosedLoop:
    def test_stats_shape_and_counts(self):
        calls = []

        def send(index):
            calls.append(index)

        stats = run_closed_loop(
            send, clients=3, requests_per_client=5, rows_per_request=4
        )
        assert len(calls) == 15
        assert stats["clients"] == 3 and stats["requests"] == 15
        assert stats["predictions_per_s"] == pytest.approx(
            stats["requests_per_s"] * 4, rel=0.02
        )
        for key in ("wall_s", "p50_ms", "p99_ms", "mean_ms"):
            assert stats[key] >= 0

    def test_sessions_closed_when_a_client_errors(self):
        """One failing client must not strand ANY session — its own
        included — half open."""
        sessions = []

        def session_factory(index):
            session = _TrackingSession(index)
            sessions.append(session)
            return session

        def send(index, session):
            if index == 1:
                raise RuntimeError("replica gone")

        with pytest.raises(RuntimeError, match="replica gone"):
            run_closed_loop(
                send,
                clients=4,
                requests_per_client=3,
                session_factory=session_factory,
            )
        assert len(sessions) == 4
        assert all(session.closed for session in sessions)

    def test_setup_failure_aborts_the_barrier(self):
        """A session_factory that raises must surface ITS error (not a
        BrokenBarrierError) and never deadlock the start barrier."""
        created = []

        def session_factory(index):
            if index == 2:
                raise ConnectionRefusedError("nobody listening")
            session = _TrackingSession(index)
            created.append(session)
            return session

        finished = threading.Event()
        failure = {}

        def run():
            try:
                run_closed_loop(
                    lambda index, session: None,
                    clients=3,
                    requests_per_client=2,
                    session_factory=session_factory,
                )
            except BaseException as error:  # noqa: BLE001
                failure["error"] = error
            finished.set()

        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        assert finished.wait(timeout=10), "closed loop deadlocked"
        assert isinstance(failure["error"], ConnectionRefusedError)
        assert all(session.closed for session in created)

    def test_session_is_passed_back_to_send(self):
        seen = {}

        def send(index, session):
            seen[index] = session

        run_closed_loop(
            send,
            clients=2,
            requests_per_client=1,
            session_factory=_TrackingSession,
        )
        assert {index: s.index for index, s in seen.items()} == {0: 0, 1: 1}


class TestHttpPredictSender:
    def test_clients_spread_across_targets(self):
        targets = ["127.0.0.1:5010", "http://127.0.0.1:5011"]
        _, session_factory = http_predict_sender(
            targets, "m_prediction_lr", [[1.0]]
        )
        # HTTPConnection connects lazily: inspecting placement is free
        spread = [session_factory(i).target for i in range(4)]
        assert spread == [targets[0], targets[1], targets[0], targets[1]]

    def test_needs_at_least_one_target(self):
        with pytest.raises(ValueError, match="at least one target"):
            http_predict_sender([], "m", [[1.0]])

    def test_non_200_raises_unless_observed(self):
        app = WebApp("stub")

        @app.route("/models/<model_name>/predict", methods=("POST",))
        def predict(request, model_name):
            return {"result": "no_replicas", "model": model_name}, 503

        server = ServerThread(app, "127.0.0.1", 0).start()
        try:
            target = f"127.0.0.1:{server.port}"
            send, factory = http_predict_sender(
                [target], "m_prediction_lr", [[1.0]], timeout_s=10.0
            )
            session = factory(0)
            try:
                with pytest.raises(RuntimeError, match="HTTP 503"):
                    send(0, session)
            finally:
                session.close()
            # an observer sees every answer and suppresses the raise —
            # the chaos drills assert on the collected statuses
            observed = []
            send, factory = http_predict_sender(
                [target],
                "m_prediction_lr",
                [[1.0]],
                timeout_s=10.0,
                on_response=lambda status, body: observed.append(
                    (status, body)
                ),
            )
            session = factory(0)
            try:
                send(0, session)
            finally:
                session.close()
            assert observed == [
                (503, {"result": "no_replicas", "model": "m_prediction_lr"})
            ]
        finally:
            server.stop()

    def test_session_reconnects_after_server_side_close(self):
        """A stale keep-alive (the server restarted between requests)
        costs one reconnect, not a failed client."""
        app = WebApp("stub")

        @app.route("/models/<model_name>/predict", methods=("POST",))
        def predict(request, model_name):
            return {"result": {"model": model_name}}, 200

        server = ServerThread(app, "127.0.0.1", 0).start()
        port = server.port
        session = HttpSession(f"127.0.0.1:{port}", timeout_s=10.0)
        try:
            status, _ = session.post_json(
                "/models/m/predict", {"rows": [[1.0]]}
            )
            assert status == 200
            # sever the server side; the session's socket goes stale
            server.stop()
            server = ServerThread(app, "127.0.0.1", port).start()
            status, body = session.post_json(
                "/models/m/predict", {"rows": [[1.0]]}
            )
            assert status == 200
            assert body == {"result": {"model": "m"}}
        finally:
            session.close()
            server.stop()
