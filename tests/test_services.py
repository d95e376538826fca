"""REST services: route/status/error-string parity with the reference."""

import json

import pytest

from learningorchestra_tpu.core.ingest import ingest_csv, write_ingest_metadata
from learningorchestra_tpu.core.jobs import JobManager
from learningorchestra_tpu.services import (
    data_type_handler,
    database_api,
    histogram,
    images,
    model_builder,
    projection,
)


def body(response):
    return json.loads(response.get_data())


@pytest.fixture()
def ingested(store, titanic_csv):
    write_ingest_metadata(store, "titanic", titanic_csv)
    ingest_csv(store, "titanic", titanic_csv)
    return store


class TestDatabaseApi:
    def test_create_file_async_and_read(self, store, titanic_csv):
        jobs = JobManager()
        client = database_api.create_app(store, jobs).test_client()
        response = client.post(
            "/files", json={"filename": "titanic", "url": titanic_csv}
        )
        assert response.status_code == 201
        assert body(response) == {"result": "file_created"}
        jobs.wait("ingest:titanic", timeout=30)
        response = client.get("/files/titanic?skip=0&limit=1&query={}")
        assert response.status_code == 200
        meta = body(response)["result"][0]
        assert meta["finished"] is True and meta["filename"] == "titanic"

    def test_jobs_endpoint(self, store, titanic_csv):
        jobs = JobManager()
        client = database_api.create_app(store, jobs).test_client()
        assert body(client.get("/jobs")) == {"result": []}
        client.post("/files", json={"filename": "titanic", "url": titanic_csv})
        jobs.wait("ingest:titanic", timeout=30)
        listing = body(client.get("/jobs"))["result"]
        assert len(listing) == 1
        job = listing[0]
        assert job["name"] == "ingest:titanic"
        assert job["state"] == "finished"

    def test_invalid_url_406(self, store, tmp_path):
        bad = tmp_path / "bad.html"
        bad.write_text("<html></html>")
        client = database_api.create_app(store).test_client()
        response = client.post(
            "/files", json={"filename": "x", "url": str(bad)}
        )
        assert response.status_code == 406
        assert body(response) == {"result": "invalid_url"}

    def test_duplicate_409(self, ingested, titanic_csv):
        client = database_api.create_app(ingested).test_client()
        response = client.post(
            "/files", json={"filename": "titanic", "url": titanic_csv}
        )
        assert response.status_code == 409
        assert body(response) == {"result": "duplicate_file"}

    def test_pagination_cap_20(self, store, tmp_path):
        csv = tmp_path / "wide.csv"
        csv.write_text("a\n" + "\n".join(str(i) for i in range(50)))
        jobs = JobManager()
        client = database_api.create_app(store, jobs).test_client()
        client.post("/files", json={"filename": "wide", "url": str(csv)})
        jobs.wait("ingest:wide", timeout=30)
        response = client.get("/files/wide?skip=0&limit=100&query={}")
        assert len(body(response)["result"]) == 20

    def test_read_resume_and_delete(self, ingested):
        client = database_api.create_app(ingested).test_client()
        listing = body(client.get("/files"))["result"]
        assert listing and "_id" not in listing[0]
        response = client.delete("/files/titanic")
        assert response.status_code == 200
        assert body(response) == {"result": "deleted_file"}
        assert "titanic" not in ingested.list_collections()


class TestProjection:
    def test_created(self, ingested):
        client = projection.create_app(ingested).test_client()
        response = client.post(
            "/projections/titanic",
            json={"projection_filename": "proj", "fields": ["Name", "Age"]},
        )
        assert response.status_code == 201
        assert body(response) == {"result": "created_file"}
        assert ingested.is_finished("proj")

    def test_duplicate_409(self, ingested):
        client = projection.create_app(ingested).test_client()
        response = client.post(
            "/projections/titanic",
            json={"projection_filename": "titanic", "fields": ["Name"]},
        )
        assert response.status_code == 409
        assert body(response) == {"result": "duplicate_file"}

    def test_invalid_parent_406(self, ingested):
        client = projection.create_app(ingested).test_client()
        response = client.post(
            "/projections/nope",
            json={"projection_filename": "p", "fields": ["Name"]},
        )
        assert response.status_code == 406
        assert body(response) == {"result": "invalid_filename"}

    def test_missing_and_invalid_fields_406(self, ingested):
        client = projection.create_app(ingested).test_client()
        response = client.post(
            "/projections/titanic",
            json={"projection_filename": "p", "fields": []},
        )
        assert body(response) == {"result": "missing_fields"}
        assert response.status_code == 406
        response = client.post(
            "/projections/titanic",
            json={"projection_filename": "p", "fields": ["Nope"]},
        )
        assert body(response) == {"result": "invalid_fields"}
        assert response.status_code == 406


class TestDataTypeHandler:
    def test_changed(self, ingested):
        client = data_type_handler.create_app(ingested).test_client()
        response = client.patch("/fieldtypes/titanic", json={"Age": "number"})
        assert response.status_code == 200
        assert body(response) == {"result": "file_changed"}

    def test_errors(self, ingested):
        client = data_type_handler.create_app(ingested).test_client()
        assert body(client.patch("/fieldtypes/nope", json={"Age": "number"})) == {
            "result": "invalid_filename"
        }
        assert body(client.patch("/fieldtypes/titanic", json={})) == {
            "result": "missing_fields"
        }
        assert body(
            client.patch("/fieldtypes/titanic", json={"Age": "boolean"})
        ) == {"result": "invalid_fields"}


class TestHistogram:
    def test_created(self, ingested):
        client = histogram.create_app(ingested).test_client()
        response = client.post(
            "/histograms/titanic",
            json={"histogram_filename": "hist", "fields": ["Sex"]},
        )
        assert response.status_code == 201
        assert body(response) == {"result": "created_file"}

    def test_duplicate_uses_histogram_string(self, ingested):
        client = histogram.create_app(ingested).test_client()
        response = client.post(
            "/histograms/titanic",
            json={"histogram_filename": "titanic", "fields": ["Sex"]},
        )
        assert response.status_code == 409
        assert body(response) == {"result": "duplicated_filename"}


class TestModelBuilder:
    def test_validator_errors(self, ingested):
        client = model_builder.create_app(ingested).test_client()
        response = client.post(
            "/models",
            json={
                "training_filename": "nope",
                "test_filename": "titanic",
                "preprocessor_code": "",
                "classificators_list": ["lr"],
            },
        )
        assert response.status_code == 406
        assert body(response) == {"result": "invalid_training_filename"}
        response = client.post(
            "/models",
            json={
                "training_filename": "titanic",
                "test_filename": "nope",
                "preprocessor_code": "",
                "classificators_list": ["lr"],
            },
        )
        assert body(response) == {"result": "invalid_test_filename"}
        response = client.post(
            "/models",
            json={
                "training_filename": "titanic",
                "test_filename": "titanic",
                "preprocessor_code": "",
                "classificators_list": ["svm"],
            },
        )
        assert body(response) == {"result": "invalid_classificator_name"}


class TestImagesService:
    @pytest.fixture()
    def numeric_store(self, store):
        from learningorchestra_tpu.core.table import ColumnTable, write_table
        import numpy as np

        rng = np.random.default_rng(0)
        table = ColumnTable.from_lists(
            {
                "a": rng.normal(size=40).tolist(),
                "b": rng.normal(size=40).tolist(),
                "Survived": rng.integers(0, 2, size=40).astype(float).tolist(),
            }
        )
        write_table(
            store,
            "numbers",
            table,
            {"filename": "numbers", "finished": True, "fields": ["a", "b", "Survived"]},
        )
        return store

    def test_pca_create_get_delete(self, numeric_store, tmp_path):
        client = images.create_app(numeric_store, str(tmp_path), "pca").test_client()
        response = client.post(
            "/images/numbers",
            json={"pca_filename": "img", "label_name": "Survived"},
        )
        assert response.status_code == 201
        assert body(response) == {"result": "created_file"}
        listing = body(client.get("/images"))["result"]
        assert listing == ["img.png"]
        response = client.get("/images/img")
        assert response.status_code == 200
        assert response.get_data()[:4] == b"\x89PNG"
        response = client.post(
            "/images/numbers", json={"pca_filename": "img", "label_name": None}
        )
        assert response.status_code == 409
        assert body(response) == {"result": "duplicate_file"}
        response = client.delete("/images/img")
        assert response.status_code == 200
        response = client.get("/images/img")
        assert response.status_code == 404
        assert body(response) == {"result": "file_not_found"}

    def test_invalid_label_406(self, numeric_store, tmp_path):
        client = images.create_app(numeric_store, str(tmp_path), "pca").test_client()
        response = client.post(
            "/images/numbers", json={"pca_filename": "i2", "label_name": "nope"}
        )
        assert response.status_code == 406
        assert body(response) == {"result": "invalid_field"}

    def test_listing_hides_inflight_claim_markers(self, numeric_store, tmp_path):
        client = images.create_app(numeric_store, str(tmp_path), "pca").test_client()
        (tmp_path / "pending.png.part").touch()  # simulated in-flight create
        assert body(client.get("/images"))["result"] == []
        response = client.get("/images/pending")
        assert response.status_code == 404

    def test_claim_never_overwrites_finished_png(self, numeric_store, tmp_path):
        client = images.create_app(numeric_store, str(tmp_path), "pca").test_client()
        response = client.post(
            "/images/numbers", json={"pca_filename": "img", "label_name": "Survived"}
        )
        assert response.status_code == 201
        rendered = (tmp_path / "img.png").read_bytes()
        # Simulate the race: name_taken() saw nothing (a concurrent
        # winner finished in the window), the marker is acquired, but the
        # PNG exists — the loser must 409 and leave the image untouched.
        import unittest.mock

        with unittest.mock.patch.object(images.os, "listdir", return_value=[]):
            response = client.post(
                "/images/numbers",
                json={"pca_filename": "img", "label_name": "Survived"},
            )
        assert response.status_code == 409
        assert body(response) == {"result": "duplicate_file"}
        assert (tmp_path / "img.png").read_bytes() == rendered
        assert not (tmp_path / "img.png.part").exists()


class TestQueryPassThrough:
    def test_operator_query_over_rest(self, ingested):
        client = database_api.create_app(ingested).test_client()
        query = json.dumps({"_id": {"$gt": 0, "$lte": 3}})
        response = client.get(f"/files/titanic?limit=20&query={query}")
        assert response.status_code == 200
        rows = body(response)["result"]
        assert [r["_id"] for r in rows] == [1, 2, 3]

    def test_in_operator_on_string_field(self, ingested):
        client = database_api.create_app(ingested).test_client()
        query = json.dumps({"Sex": {"$in": ["female"]}})
        response = client.get(f"/files/titanic?limit=20&query={query}")
        rows = body(response)["result"]
        assert rows and all(r["Sex"] == "female" for r in rows)


class TestConcurrentCreate:
    def test_duplicate_projection_one_winner(self, ingested):
        """The check-then-act race SURVEY §5 flags: concurrent duplicate
        creates must produce exactly one 201 and one 409 — never a 500."""
        import threading

        app = projection.create_app(ingested)
        results = []
        barrier = threading.Barrier(2)

        def create():
            client = app.test_client()
            barrier.wait()
            response = client.post(
                "/projections/titanic",
                json={"projection_filename": "race_proj", "fields": ["Name"]},
            )
            results.append(response.status_code)

        threads = [threading.Thread(target=create) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(results) == [201, 409]

    def test_duplicate_histogram_one_winner(self, ingested):
        import threading

        app = histogram.create_app(ingested)
        results = []
        barrier = threading.Barrier(2)

        def create():
            client = app.test_client()
            barrier.wait()
            response = client.post(
                "/histograms/titanic",
                json={"histogram_filename": "race_hist", "fields": ["Sex"]},
            )
            results.append(response.status_code)

        threads = [threading.Thread(target=create) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(results) == [201, 409]


class TestImageFilenameSafety:
    def test_traversal_rejected_on_create(self, store, tmp_path):
        client = images.create_app(store, str(tmp_path), "pca").test_client()
        for bad in ("../evil", "a/b", "..", ""):
            response = client.post(
                "/images/whatever", json={"pca_filename": bad, "label_name": None}
            )
            assert response.status_code == 406, bad
            assert body(response) == {"result": "invalid_filename"}
        assert list(tmp_path.parent.glob("*.png")) == []

    def test_traversal_rejected_on_get_delete(self, store, tmp_path):
        outside = tmp_path / "secret.png"
        outside.write_bytes(b"\x89PNG....")
        images_dir = tmp_path / "imgs"
        client = images.create_app(store, str(images_dir), "pca").test_client()
        response = client.get("/images/..%2Fsecret")
        assert response.status_code == 404
        response = client.delete("/images/..%2Fsecret")
        assert response.status_code == 404
        assert outside.exists()


class TestQueryErrors:
    def test_unsupported_operator_400(self, ingested):
        client = database_api.create_app(ingested).test_client()
        query = json.dumps({"Name": {"$text": "x"}})
        response = client.get(f"/files/titanic?limit=5&query={query}")
        assert response.status_code == 400
        assert "unsupported query operator" in body(response)["result"]

    def test_or_query_over_rest(self, ingested):
        client = database_api.create_app(ingested).test_client()
        query = json.dumps({"$or": [{"_id": 1}, {"_id": 4}]})
        response = client.get(f"/files/titanic?limit=20&query={query}")
        rows = body(response)["result"]
        assert [r["_id"] for r in rows] == [1, 4]


class TestInFlightImageClaim:
    def test_placeholder_invisible_to_get_and_delete(self, store, tmp_path, monkeypatch):
        """While a create is computing, GET/DELETE must 404 (no 0-byte
        PNG leak) and a concurrent duplicate POST must 409."""
        import threading

        from learningorchestra_tpu.core.table import ColumnTable, write_table
        import numpy as np

        rng = np.random.default_rng(0)
        table = ColumnTable.from_lists(
            {"a": rng.normal(size=20).tolist(), "b": rng.normal(size=20).tolist()}
        )
        write_table(
            store, "n", table, {"filename": "n", "finished": True, "fields": ["a", "b"]}
        )
        app = images.create_app(store, str(tmp_path), "pca")
        client = app.test_client()

        entered = threading.Event()
        release = threading.Event()
        import learningorchestra_tpu.services.images as images_module

        real_create = images_module.create_embedding_image

        def slow_create(*args, **kwargs):
            entered.set()
            release.wait(timeout=10)
            return real_create(*args, **kwargs)

        monkeypatch.setattr(images_module, "create_embedding_image", slow_create)

        result = {}

        def do_create():
            result["create"] = app.test_client().post(
                "/images/n", json={"pca_filename": "slow", "label_name": None}
            )

        t = threading.Thread(target=do_create)
        t.start()
        assert entered.wait(timeout=10)
        assert client.get("/images/slow").status_code == 404
        assert client.delete("/images/slow").status_code == 404
        dup = client.post("/images/n", json={"pca_filename": "slow", "label_name": None})
        assert dup.status_code == 409
        release.set()
        t.join(timeout=30)
        assert result["create"].status_code == 201
        assert client.get("/images/slow").status_code == 200
        # claim marker cleaned up
        assert sorted(p.name for p in tmp_path.iterdir()) == ["slow.png"]


class TestMalformedQueries400:
    def test_unparseable_and_nondict_queries(self, ingested):
        client = database_api.create_app(ingested).test_client()
        for bad in ("hello", "5", "[1,2]"):
            response = client.get(f"/files/titanic?limit=5&query={bad}")
            assert response.status_code == 400, bad
        response = client.get("/files/titanic?limit=abc")
        assert response.status_code == 400

    def test_malformed_operands(self, ingested):
        client = database_api.create_app(ingested).test_client()
        bads = [
            {"a": {"$nin": 5}},
            {"s": {"$regex": "("}},
            {"a": {"$not": 5}},
            {"$or": {"a": 1}},
            {"a": {"$in": 3}},
        ]
        for bad in bads:
            response = client.get(
                f"/files/titanic?limit=5&query={json.dumps(bad)}"
            )
            assert response.status_code == 400, bad


class TestAsyncModelBuild:
    @pytest.fixture()
    def store_with_numeric_dataset(self, store):
        from learningorchestra_tpu.core.table import write_columns

        write_columns(
            store,
            "numbers",
            {
                "a": [float(i % 7) for i in range(240)],
                "b": [float((i * 3) % 5) for i in range(240)],
                "label": [float(i % 2) for i in range(240)],
            },
            {"filename": "numbers", "finished": True,
             "fields": ["a", "b", "label"]},
        )
        return store

    def test_async_build_returns_immediately_and_tracks_job(
        self, store_with_numeric_dataset
    ):
        import json as _json
        import time as _time

        from learningorchestra_tpu.services import model_builder

        store = store_with_numeric_dataset
        app = model_builder.create_app(store).test_client()
        body = {
            "training_filename": "numbers",
            "test_filename": "numbers",
            "preprocessor_code": (
                "from pyspark.ml.feature import VectorAssembler\n"
                "assembler = VectorAssembler(inputCols=['a', 'b'],"
                " outputCol='features')\n"
                "features_training = assembler.transform(training_df)\n"
                "features_testing = assembler.transform(testing_df)\n"
                "features_evaluation = None\n"
            ),
            "classificators_list": ["nb"],
            "async": True,
        }
        response = app.post("/models", json=body)
        assert response.status_code == 201
        payload = _json.loads(response.get_data())
        job_name = payload["job"]

        deadline = _time.time() + 120
        while _time.time() < deadline:
            jobs = _json.loads(app.get("/jobs").get_data())["result"]
            record = next(j for j in jobs if j["name"] == job_name)
            if record["state"] in ("finished", "failed"):
                break
            _time.sleep(0.2)
        else:
            raise AssertionError(f"async build never completed: {record}")
        assert record["state"] == "finished", record
        assert "numbers_prediction_nb" in store.list_collections()

    def test_async_build_failure_reported_in_jobs(
        self, store_with_numeric_dataset
    ):
        import json as _json
        import time as _time

        from learningorchestra_tpu.services import model_builder

        store = store_with_numeric_dataset
        app = model_builder.create_app(store).test_client()
        response = app.post(
            "/models",
            json={
                "training_filename": "numbers",
                "test_filename": "numbers",
                "preprocessor_code": "this is not python",
                "classificators_list": ["nb"],
                "async": True,
            },
        )
        assert response.status_code == 201
        deadline = _time.time() + 60
        while _time.time() < deadline:
            jobs = _json.loads(app.get("/jobs").get_data())["result"]
            record = jobs[-1]
            if record["state"] in ("finished", "failed"):
                break
            _time.sleep(0.2)
        assert record["state"] == "failed"
        assert record["error"]


def test_runner_entry_point_boots_in_default_topology(tmp_path):
    """``python -m learningorchestra_tpu.services.runner`` with no
    ``LO_STORE_URL`` — the documented Quick start. Every other test
    enters through ``start_all`` or the supervisor (which always sets
    the URL), which is how a boot banner that crashed on the unset
    variable shipped unnoticed."""
    import os
    import subprocess
    import sys
    import threading

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if not k.startswith("LO_")}
    env.update(
        PYTHONPATH=repo,
        PYTHONUNBUFFERED="1",
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jit"),
        LO_EPHEMERAL="1",  # the suite cannot assume 5000-5006 are free
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "learningorchestra_tpu.services.runner"],
        cwd=tmp_path,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    lines: list = []
    serving = threading.Event()

    def pump():
        for line in proc.stdout:
            lines.append(line)
            if "serving all services" in line:
                serving.set()

    thread = threading.Thread(target=pump, daemon=True)
    thread.start()
    try:
        assert serving.wait(90), "".join(lines)[-2000:]
    finally:
        proc.kill()
        proc.wait(timeout=10)
        thread.join(timeout=10)
    boot = "".join(lines)
    # what JAX brought up, not just what the environment asked for
    assert 'device: platform=cpu kind="cpu"' in boot
    assert f"compile cache: dir={tmp_path / 'jit'}" in boot
    assert "csv parser: " in boot
