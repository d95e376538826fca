"""deploy/stack.py brings up the real topology: store server + seven
service processes, health-gated, with restart-on-failure.

This is the deployment story the reference gets from Docker swarm
(restart_policy docker-compose.yml:14-15, dockerize -wait :145,
services :173-330) — proven here with a live supervisor: the stack
comes up, serves the product path, and a killed service is restarted
and serves again."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_TESTS_DIR)


def _get(url, timeout=5):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as error:
        return error.code, error.read()


@pytest.mark.integration
def test_stack_bringup_serve_and_restart(tmp_path):
    data_dir = tmp_path / "stack_data"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["LO_EPHEMERAL"] = "1"
    env["LO_STORE_PORT"] = "0"
    env["LO_RESTART_DELAY"] = "0.5"
    supervisor = subprocess.Popen(
        [sys.executable, os.path.join(_REPO_ROOT, "deploy", "stack.py"),
         str(data_dir)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        cwd=_REPO_ROOT,
    )
    ports_path = data_dir / "stack_ports.json"
    try:
        # Bring-up: all eight children publish ports (jax import per
        # process dominates; generous deadline).
        deadline = time.time() + 300
        state = None
        while time.time() < deadline:
            if supervisor.poll() is not None:
                out = supervisor.stdout.read()
                raise AssertionError(f"supervisor died:\n{out}")
            if ports_path.exists():
                state = json.loads(ports_path.read_text())
                if len(state["ports"]) == 8:
                    break
            time.sleep(0.5)
        assert state is not None and len(state["ports"]) == 8, state

        # The stack serves: database_api answers through the store.
        db_port = state["ports"]["database_api"]
        status, body = _get(f"http://127.0.0.1:{db_port}/files")
        assert status == 200
        assert body == {"result": []}

        # Kill a service ungracefully; the supervisor restarts it and
        # it serves again (possibly on a new ephemeral port).
        victim_pid = state["pids"]["histogram"]
        old_port = state["ports"]["histogram"]
        os.kill(victim_pid, signal.SIGKILL)
        deadline = time.time() + 120
        reborn = None
        while time.time() < deadline:
            state = json.loads(ports_path.read_text())
            pid = state["pids"].get("histogram")
            if pid and pid != victim_pid:
                reborn = state["ports"]["histogram"]
                break
            time.sleep(0.5)
        assert reborn is not None, "histogram was not restarted"
        status, body = _get(f"http://127.0.0.1:{reborn}/histograms")
        assert status in (200, 404, 405)  # reachable — route surface up
        # the store kept state across the service bounce
        status, body = _get(f"http://127.0.0.1:{db_port}/files")
        assert status == 200
        del old_port
    finally:
        supervisor.send_signal(signal.SIGTERM)
        try:
            supervisor.wait(30)
        except subprocess.TimeoutExpired:
            supervisor.kill()


@pytest.mark.integration
def test_stack_multihost_build_and_worker_death(tmp_path):
    """LO_WORKERS=1: the supervisor brings up store + coordinator + one
    SPMD worker as ONE jax.distributed runtime, a model build runs over
    the REST surface on the cross-process mesh, and killing the worker
    restarts the WHOLE group (a lost member poisons the collective
    stream) after which the next build succeeds — the swarm-restart +
    Spark-application-restart story in one supervisor."""
    data_dir = tmp_path / "mh_data"
    csv_path = tmp_path / "mh.csv"
    with open(csv_path, "w") as f:
        f.write("f1,f2,label\n")
        for i in range(120):
            lab = i % 2
            f.write(f"{lab * 2 + (i % 7) * 0.1:.3f},{-lab + (i % 5) * 0.1:.3f},{lab}\n")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["LO_EPHEMERAL"] = "1"
    env["LO_STORE_PORT"] = "0"
    env["LO_RESTART_DELAY"] = "0.5"
    env["LO_WORKERS"] = "1"
    env["LO_COORD_PORT"] = "0"  # replaced below — needs a real free port
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        env["LO_COORD_PORT"] = str(s.getsockname()[1])
    supervisor = subprocess.Popen(
        [sys.executable, os.path.join(_REPO_ROOT, "deploy", "stack.py"),
         str(data_dir)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        cwd=_REPO_ROOT,
        start_new_session=True,  # one process group: no orphaned runners
    )
    ports_path = data_dir / "stack_ports.json"

    def wait_state(min_ports: int, deadline_s: float) -> dict:
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if supervisor.poll() is not None:
                out = supervisor.stdout.read()
                raise AssertionError(f"supervisor died:\n{out}")
            if ports_path.exists():
                state = json.loads(ports_path.read_text())
                if len(state["ports"]) >= min_ports and "worker1" in state["pids"]:
                    return state
            time.sleep(0.5)
        raise AssertionError("stack never published the runtime ports")

    def post(url, body, timeout=300):
        data = json.dumps(body).encode()
        request = urllib.request.Request(
            url, data=data, headers={"Content-Type": "application/json"}
        )
        with urllib.request.urlopen(request, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())

    def build_once(state, name: str) -> None:
        db = state["ports"]["database_api"]
        mb = state["ports"]["model_builder"]
        dt = state["ports"]["data_type_handler"]
        status, _ = post(
            f"http://127.0.0.1:{db}/files",
            {"filename": name, "url": str(csv_path)},
        )
        assert status == 201
        deadline = time.time() + 60
        while time.time() < deadline:
            status, body = _get(
                f"http://127.0.0.1:{db}/files/{name}?skip=0&limit=1&query={{}}"
            )
            if status == 200 and body["result"][0].get("finished"):
                break
            time.sleep(0.2)
        else:
            raise AssertionError(f"ingest of {name} never finished")
        request = urllib.request.Request(
            f"http://127.0.0.1:{dt}/fieldtypes/{name}",
            data=json.dumps(
                {"f1": "number", "f2": "number", "label": "number"}
            ).encode(),
            headers={"Content-Type": "application/json"},
            method="PATCH",
        )
        with urllib.request.urlopen(request, timeout=60) as resp:
            assert resp.status == 200
        pre = (
            "from pyspark.ml.feature import VectorAssembler\n"
            "assembler = VectorAssembler(inputCols=['f1', 'f2'],"
            " outputCol='features')\n"
            "features_training = assembler.transform(training_df)\n"
            "features_testing = assembler.transform(testing_df)\n"
            "features_evaluation = features_training\n"
        )
        status, _ = post(
            f"http://127.0.0.1:{mb}/models",
            {
                "training_filename": name,
                "test_filename": name,
                "preprocessor_code": pre,
                "classificators_list": ["lr"],
            },
        )
        assert status == 201
        status, body = _get(
            f"http://127.0.0.1:{db}/files/{name}_prediction_lr"
            "?skip=0&limit=1&query={}"
        )
        assert status == 200
        assert float(body["result"][0]["accuracy"]) > 0.8

    try:
        state = wait_state(8, 420)
        build_once(state, "mh_a")

        # kill the worker: the whole runtime group must restart
        os.kill(state["pids"]["worker1"], signal.SIGKILL)
        old_coord_pid = state["pids"]["coordinator"]
        deadline = time.time() + 420
        while time.time() < deadline:
            fresh = wait_state(8, 420)
            if fresh["pids"]["coordinator"] != old_coord_pid:
                state = fresh
                break
            time.sleep(0.5)
        else:
            raise AssertionError("group never restarted after worker death")

        build_once(state, "mh_b")
    finally:
        supervisor.send_signal(signal.SIGTERM)
        try:
            out, _ = supervisor.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            supervisor.kill()
            out, _ = supervisor.communicate()
        # a supervisor killed mid-bring-up can leave runner children
        # behind; sweep the whole process group
        try:
            os.killpg(supervisor.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def test_child_that_lost_its_device_ends_the_wait_at_once():
    """A second chip owner exits at boot with the runner's
    EXIT_NO_DEVICE; the supervisor must say so immediately instead of
    running out a 120 s port wait (and must not restart it)."""
    import importlib.util

    from learningorchestra_tpu.services import runner

    spec = importlib.util.spec_from_file_location(
        "lo_stack", os.path.join(_REPO_ROOT, "deploy", "stack.py")
    )
    stack = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stack)
    assert stack.EXIT_NO_DEVICE == runner.EXIT_NO_DEVICE

    child = stack.Child(
        "tsne",
        [sys.executable, "-c", f"raise SystemExit({runner.EXIT_NO_DEVICE})"],
        dict(os.environ),
        lambda line: None,
    )
    child.start()
    start = time.monotonic()
    with pytest.raises(stack.ChildExited, match="exited rc=69"):
        child.wait_port(60)
    assert time.monotonic() - start < 30
