"""Device-resident data plane: rev-keyed cache invalidation (local and
over the wire), LRU capacity bounds, streaming-read fault recovery,
wire compression, and the builder's overlapped write-back."""

import threading
import zlib

import numpy as np
import pytest
import requests

from learningorchestra_tpu.core import devcache
from learningorchestra_tpu.core.devcache import DeviceCache
from learningorchestra_tpu.core.store import InMemoryStore, ROW_ID
from learningorchestra_tpu.core.store_service import (
    RemoteStore,
    create_store_app,
)
from learningorchestra_tpu.core.wire import (
    ACCEPT_HEADER,
    CONTENT_TYPE,
    ENCODING_HEADER,
    decode_frame,
    encode_frame,
)
from learningorchestra_tpu.utils.web import ServerThread


@pytest.fixture(autouse=True)
def clean_global_devcache():
    devcache.reset_global_devcache()
    yield
    devcache.reset_global_devcache()


@pytest.fixture()
def remote_store():
    server = ServerThread(
        create_store_app(InMemoryStore()), "127.0.0.1", 0
    ).start()
    yield RemoteStore(f"http://127.0.0.1:{server.port}")
    server.stop()


def seed_dataset(store) -> None:
    store.create_collection("ds")
    store.insert_one("ds", {ROW_ID: 0, "filename": "ds", "finished": True})
    store.insert_columns("ds", {"a": [1.0, 2.0, 3.0], "b": [4.0, 5.0, 6.0]})


# Every mutating store op, as (name, mutate, expected_value_of_a).
# The drop case expects an empty reload instead of values.
MUTATIONS = [
    ("insert_one", lambda s: s.insert_one("ds", {ROW_ID: 99, "a": 9.0})),
    (
        "insert_many",
        lambda s: s.insert_many("ds", [{ROW_ID: 100, "a": 8.0}]),
    ),
    ("insert_columns", lambda s: s.insert_columns("ds", {"a": [7.0]})),
    ("set_column", lambda s: s.set_column("ds", "a", [9.0, 9.0, 9.0])),
    (
        "set_field_values",
        lambda s: s.set_field_values("ds", "a", {1: 42.0}),
    ),
    ("update_one", lambda s: s.update_one("ds", {ROW_ID: 1}, {"a": 5.5})),
    ("drop", lambda s: s.drop("ds")),
]


class TestRevInvalidation:
    @pytest.mark.parametrize("name,mutate", MUTATIONS)
    def test_local_mutation_bumps_rev_and_evicts(self, name, mutate):
        store = InMemoryStore()
        seed_dataset(store)
        cache = DeviceCache(capacity=10_000_000)
        first = devcache.dataset_table(store, "ds", cache=cache)
        assert devcache.dataset_table(store, "ds", cache=cache) is first
        rev_before = store.collection_rev("ds")
        invalidations_before = cache.stats()["invalidations"]

        mutate(store)
        rev_after = store.collection_rev("ds")
        assert rev_after != rev_before  # every mutating op bumps (or -1)

        reloaded = devcache.dataset_table(store, "ds", cache=cache)
        assert reloaded is not first  # the stale entry was evicted
        assert cache.stats()["invalidations"] > invalidations_before
        if name == "drop":
            assert rev_after == -1
            assert reloaded.num_rows == 0
            # unknown rev: nothing was re-cached
            assert cache.stats()["entries"] == 0
        else:
            # the reload sees the mutation and is cached under the new rev
            assert devcache.dataset_table(store, "ds", cache=cache) is reloaded

    def test_rev_is_store_monotonic_across_drop_recreate(self):
        """A dropped-and-recreated collection must never reissue a rev a
        cache still holds — revs come from a store-wide sequence."""
        store = InMemoryStore()
        seed_dataset(store)
        rev_first = store.collection_rev("ds")
        store.drop("ds")
        seed_dataset(store)
        assert store.collection_rev("ds") > rev_first

    @pytest.mark.parametrize("name,mutate", MUTATIONS)
    def test_remote_mutation_bumps_rev_and_evicts(
        self, remote_store, name, mutate
    ):
        """The same invariant over the wire: RemoteStore probes
        GET /c/<name>/rev, so a write through ANY client evicts cached
        readers everywhere at their next lookup."""
        seed_dataset(remote_store)
        cache = DeviceCache(capacity=10_000_000)
        first = devcache.dataset_table(remote_store, "ds", cache=cache)
        assert (
            devcache.dataset_table(remote_store, "ds", cache=cache) is first
        )
        rev_before = remote_store.collection_rev("ds")

        mutate(remote_store)
        assert remote_store.collection_rev("ds") != rev_before

        reloaded = devcache.dataset_table(remote_store, "ds", cache=cache)
        assert reloaded is not first
        if name == "set_column":
            assert reloaded.columns["a"].tolist() == [9.0, 9.0, 9.0]

    def test_unknown_backend_never_caches(self):
        class NoRevStore(InMemoryStore):
            collection_rev = None

        store = NoRevStore()
        seed_dataset(store)
        cache = DeviceCache(capacity=10_000_000)
        first = devcache.dataset_table(store, "ds", cache=cache)
        second = devcache.dataset_table(store, "ds", cache=cache)
        assert first is not second
        assert cache.stats()["entries"] == 0


class TestLruBounds:
    def test_eviction_under_cap(self):
        cache = DeviceCache(capacity=100)
        for i in range(5):
            cache.put("s", f"c{i}", ("k",), rev=1, value=i, nbytes=40)
        stats = cache.stats()
        assert stats["bytes"] <= 100
        assert stats["entries"] == 2
        assert stats["evictions"] == 3
        # LRU order: the newest entries survive
        assert cache.get("s", "c4", ("k",), 1) == 4
        assert cache.get("s", "c0", ("k",), 1) is None

    def test_lookup_refreshes_recency(self):
        cache = DeviceCache(capacity=100)
        cache.put("s", "a", ("k",), 1, "a", 40)
        cache.put("s", "b", ("k",), 1, "b", 40)
        assert cache.get("s", "a", ("k",), 1) == "a"  # a is now most recent
        cache.put("s", "c", ("k",), 1, "c", 40)  # evicts b, not a
        assert cache.get("s", "a", ("k",), 1) == "a"
        assert cache.get("s", "b", ("k",), 1) is None

    def test_oversized_entry_passes_through_uncached(self):
        cache = DeviceCache(capacity=100)
        value = cache.put("s", "a", ("k",), 1, "big", nbytes=1000)
        assert value == "big"
        assert cache.stats()["entries"] == 0

    def test_zero_capacity_disables(self):
        store = InMemoryStore()
        seed_dataset(store)
        cache = DeviceCache(capacity=0)
        first = devcache.dataset_table(store, "ds", cache=cache)
        assert devcache.dataset_table(store, "ds", cache=cache) is not first


class TestConcurrentReaders:
    def test_many_threads_one_entry(self):
        store = InMemoryStore()
        seed_dataset(store)
        cache = DeviceCache(capacity=10_000_000)
        results = []
        errors = []

        def read():
            try:
                for _ in range(20):
                    table = devcache.dataset_table(store, "ds", cache=cache)
                    results.append(table.columns["a"].tolist())
            except Exception as error:  # noqa: BLE001
                errors.append(error)

        threads = [threading.Thread(target=read) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert all(values == [1.0, 2.0, 3.0] for values in results)
        stats = cache.stats()
        assert stats["entries"] == 1
        # concurrent first loads may race (both load, last put wins) but
        # the steady state is all hits
        assert stats["hits"] > 100


class TestMidStreamFault:
    def test_retry_resumes_at_failed_chunk_and_purges_cache(
        self, remote_store
    ):
        """Regression: a mid-stream chunk failure must (a) invalidate any
        partially-populated devcache entry for the collection and (b)
        retry from the FAILED chunk — chunk 0 is never re-fetched."""
        remote_store.insert_columns(
            "ds", {"x": [float(i) for i in range(25)]}
        )
        remote_store.wire_rows_bin = 7

        # a resident entry for this collection under THIS store's scope,
        # standing in for a partially-populated one; an entry for a
        # same-named collection of a DIFFERENT store must survive the
        # purge
        cache = devcache.global_devcache()
        scope = devcache.store_token(remote_store)
        cache.put(scope, "ds", ("partial",), rev=1, value="stale", nbytes=8)
        cache.put("otherstore", "ds", ("k",), rev=1, value="keep", nbytes=8)
        assert cache.stats()["entries"] == 2

        calls = []
        failed = []
        original = remote_store._fetch_frame_bytes

        def faulty(path, body):
            if path.endswith("/read_columns_bin"):
                calls.append(body["start"])
                if body["start"] == 14 and not failed:
                    failed.append(True)
                    raise requests.ConnectionError("injected mid-stream")
            return original(path, body)

        remote_store._fetch_frame_bytes = faulty
        try:
            out = remote_store.read_column_arrays("ds", ["x"])
        finally:
            remote_store._fetch_frame_bytes = original

        assert out["x"].tolist() == [float(i) for i in range(25)]
        assert calls.count(0) == 1  # never restarted from chunk 0
        assert calls.count(14) == 2  # the failed chunk, retried in place
        assert cache.get(scope, "ds", ("partial",), 1) is None  # purged
        assert cache.get("otherstore", "ds", ("k",), 1) == "keep"

    def test_exhausted_retries_surface_the_error(self, remote_store):
        remote_store.insert_columns("ds", {"x": [1.0, 2.0, 3.0]})
        remote_store.wire_rows_bin = 2
        remote_store.chunk_retries = 1
        original = remote_store._fetch_frame_bytes

        def always_fails(path, body):
            if path.endswith("/read_columns_bin") and body["start"] == 2:
                raise requests.ConnectionError("injected, persistent")
            return original(path, body)

        remote_store._fetch_frame_bytes = always_fails
        try:
            with pytest.raises(requests.ConnectionError):
                remote_store.read_column_arrays("ds", ["x"])
        finally:
            remote_store._fetch_frame_bytes = original


class TestStreamingReads:
    def test_double_buffered_read_matches_single_frame(self, remote_store):
        rows = 100
        remote_store.insert_columns(
            "ds",
            {
                "x": [float(i) for i in range(rows)],
                "s": [str(i) for i in range(rows)],
            },
        )
        full = remote_store.read_column_arrays("ds")
        remote_store.wire_rows_bin = 9  # force the paged, prefetching loop
        paged = remote_store.read_column_arrays("ds")
        assert paged["x"].tolist() == full["x"].tolist()
        assert paged["s"].tolist() == full["s"].tolist()

    def test_rev_endpoint(self, remote_store):
        assert remote_store.collection_rev("missing") == -1
        remote_store.insert_columns("ds", {"x": [1.0]})
        rev = remote_store.collection_rev("ds")
        assert rev > 0
        remote_store.insert_columns("ds", {"x": [2.0]})
        assert remote_store.collection_rev("ds") > rev


class TestWireCompression:
    def make_app(self):
        store = InMemoryStore()
        store.insert_columns(
            "ds", {"x": [float(i % 17) for i in range(5000)]}
        )
        return store, create_store_app(store).test_client()

    def test_server_compresses_only_when_advertised(self):
        _, client = self.make_app()
        body = {"fields": ["x"], "start": 0, "limit": None}
        plain = client.post("/c/ds/read_columns_bin", json=body)
        assert plain.headers.get(ENCODING_HEADER) is None
        columns, _ = decode_frame(plain.data)
        assert len(columns["x"]) == 5000

        squeezed = client.post(
            "/c/ds/read_columns_bin",
            json=body,
            headers={ACCEPT_HEADER: "zlib"},
        )
        assert squeezed.headers.get(ENCODING_HEADER) == "zlib"
        assert len(squeezed.data) < len(plain.data)
        columns, _ = decode_frame(zlib.decompress(squeezed.data))
        assert columns["x"].tolist() == [float(i % 17) for i in range(5000)]

    def test_server_accepts_compressed_uploads(self):
        from learningorchestra_tpu.core.columns import Column

        store, client = self.make_app()
        frame = encode_frame(
            {"y": Column.from_values([float(i) for i in range(5000)])},
            extra={"start_id": 1},
        )
        response = client.post(
            "/c/up/insert_columns_bin",
            data=zlib.compress(frame, 1),
            headers={
                "Content-Type": CONTENT_TYPE,
                ENCODING_HEADER: "zlib",
            },
        )
        assert response.status_code == 200
        assert store.count("up") == 5000

    def test_remote_store_round_trip_compressed(self):
        server = ServerThread(
            create_store_app(InMemoryStore()), "127.0.0.1", 0
        ).start()
        try:
            remote = RemoteStore(
                f"http://127.0.0.1:{server.port}", compress=True
            )
            values = [float(i) for i in range(5000)]
            remote.insert_columns("ds", {"x": values})
            assert remote.read_column_arrays("ds", ["x"])["x"].tolist() == (
                values
            )
        finally:
            server.stop()


class TestContentAddressedDeviceCache:
    def test_same_bytes_reuse_one_device_copy(self):
        from learningorchestra_tpu.frame.dataframe import DataFrame

        X = np.arange(48, dtype=np.float64).reshape(12, 4)
        frame_a = DataFrame({"features": X.copy()})
        frame_b = DataFrame({"features": X.copy()})  # distinct frame, same bytes
        dm_a = frame_a.device_matrix("features")
        dm_b = frame_b.device_matrix("features")
        assert dm_a is dm_b  # one H2D served both frames
        changed = DataFrame({"features": X + 1.0})
        assert changed.device_matrix("features") is not dm_a

    def test_labels_cached_by_content(self):
        from learningorchestra_tpu.frame.dataframe import DataFrame

        y = np.array([0.0, 1.0, 0.0, 1.0])
        frame_a = DataFrame({"label": y.copy()})
        frame_b = DataFrame({"label": y.copy()})
        assert frame_a.device_labels("label") is frame_b.device_labels(
            "label"
        )

    def test_embedding_inputs_cached_atomically_and_rev_keyed(self):
        store = InMemoryStore()
        seed_dataset(store)
        cache = DeviceCache(capacity=100_000_000)
        encoded, vocab, dm = devcache.dataset_embedding_inputs(
            store, "ds", cache=cache
        )
        again = devcache.dataset_embedding_inputs(store, "ds", cache=cache)
        # one atomic entry: table, vocab and device matrix hit together
        assert again[0] is encoded and again[2] is dm
        assert len(dm) == encoded.num_rows
        store.set_column("ds", "a", [7.0, 7.0, 7.0])
        reloaded = devcache.dataset_embedding_inputs(
            store, "ds", cache=cache
        )
        assert reloaded[2] is not dm
        assert reloaded[0].columns["a"].tolist() == [7.0, 7.0, 7.0]


def _lookups(work):
    """The ``devcache:lookup`` spans ``work()`` opens in a trace."""
    from learningorchestra_tpu.telemetry import tracing

    trace = tracing.Trace(name="lookups")
    with tracing.activate(trace), tracing.span("phase:evaluate"):
        work()
    (root,) = trace.as_dict()["spans"]
    return [c for c in root["children"] if c["name"] == "devcache:lookup"]


class TestLookupSpans:
    """Every lookup is one ``devcache:lookup`` span that says how the
    cache answered: a hit or a miss, the entry's bytes, and what the
    LRU dropped for it."""

    def test_a_content_lookup_misses_then_hits(self):
        from learningorchestra_tpu.parallel import default_mesh

        X = np.arange(48, dtype=np.float64).reshape(12, 4)
        mesh = default_mesh()
        first, second = _lookups(
            lambda: [
                devcache.content_device_matrix(X.copy(), mesh)
                for _ in range(2)
            ]
        )
        assert first["meta"]["kind"] == "devmat"
        assert (first["meta"]["hits"], first["meta"]["misses"]) == (0, 1)
        assert (second["meta"]["hits"], second["meta"]["misses"]) == (1, 0)
        assert first["meta"]["bytes"] == second["meta"]["bytes"] > X.size * 4
        for lookup in (first, second):
            assert lookup["meta"]["evicted"] == 0
            assert lookup["meta"]["evicted_bytes"] == 0
        # the copy nests under the miss's lookup, after its digest
        assert [c["name"] for c in first["children"]] == [
            "devcache:digest", "h2d:matrix"
        ]
        assert [c["name"] for c in second["children"]] == ["devcache:digest"]

    def test_the_digest_carries_the_bytes_hashed(self):
        from learningorchestra_tpu.parallel import default_mesh

        X = np.arange(60, dtype=np.float64).reshape(15, 4)
        y = np.array([0, 1, 1, 0, 1], dtype=np.int32)
        mesh = default_mesh()
        matrix, labels = _lookups(
            lambda: (
                devcache.content_device_matrix(X, mesh),
                devcache.content_device_labels(y, mesh),
            )
        )
        assert labels["meta"]["kind"] == "devlab"
        for lookup, array in ((matrix, X), (labels, y)):
            digest = lookup["children"][0]
            assert digest["name"] == "devcache:digest"
            assert digest["meta"]["bytes"] == array.nbytes

    def test_the_put_that_drops_an_entry_says_so(self, monkeypatch):
        from learningorchestra_tpu.parallel import default_mesh

        mesh = default_mesh()
        X = np.arange(48, dtype=np.float64).reshape(12, 4)
        (first,) = _lookups(lambda: devcache.content_device_matrix(X, mesh))
        size = first["meta"]["bytes"]
        # room for one entry of this size, not two
        monkeypatch.setattr(
            devcache.global_devcache(), "capacity", size + size // 2
        )
        (second,) = _lookups(
            lambda: devcache.content_device_matrix(X + 1.0, mesh)
        )
        assert second["meta"]["misses"] == 1
        assert second["meta"]["evicted"] == 1
        assert second["meta"]["evicted_bytes"] == size

    def test_a_table_lookup_nests_the_read_under_its_miss(self):
        store = InMemoryStore()
        seed_dataset(store)
        cache = DeviceCache(capacity=10_000_000)
        miss, hit = _lookups(
            lambda: [
                devcache.dataset_table(store, "ds", cache=cache)
                for _ in range(2)
            ]
        )
        assert miss["meta"]["kind"] == hit["meta"]["kind"] == "table"
        assert (miss["meta"]["misses"], hit["meta"]["hits"]) == (1, 1)
        assert [c["name"] for c in miss["children"]] == ["store:read"]
        assert hit["children"] == []
        assert miss["meta"]["bytes"] == hit["meta"]["bytes"] > 0


class TestEmbeddingDeviceInputs:
    def test_pca_accepts_device_matrix(self):
        from learningorchestra_tpu.ml.base import shard_matrix
        from learningorchestra_tpu.ops.pca import pca_embedding

        X = np.random.default_rng(0).random((64, 4)).astype(np.float32)
        from_host = pca_embedding(X)
        from_device = pca_embedding(shard_matrix(X))
        assert from_device.shape == (64, 2)
        np.testing.assert_allclose(from_host, from_device, atol=1e-4)

    def test_images_pipeline_hits_cache_on_second_embed(self, tmp_path):
        from learningorchestra_tpu.ops.images import create_embedding_image

        store = InMemoryStore()
        seed_dataset(store)
        create_embedding_image(
            store, "ds", None, "first", str(tmp_path), "pca", render=False
        )
        stats_after_first = devcache.global_devcache().stats()
        create_embedding_image(
            store, "ds", None, "second", str(tmp_path), "pca", render=False
        )
        stats_after_second = devcache.global_devcache().stats()
        # second embed: ONE atomic hit serves the encoded table + device
        # matrix together (the raw table read lives inside its loader,
        # which never runs again)
        assert (
            stats_after_second["hits"] >= stats_after_first["hits"] + 1
        )
        assert (
            stats_after_second["misses"] == stats_after_first["misses"]
        )


def _build_tiny(store, overlap: str, classifiers=("nb", "dt")):
    import os

    from learningorchestra_tpu.ml.builder import build_model

    preprocessor = (
        "from pyspark.ml.feature import VectorAssembler\n"
        "cols = [c for c in training_df.schema.names if c != 'label']\n"
        "assembler = VectorAssembler(inputCols=cols, outputCol='features')\n"
        "features_training = assembler.transform(training_df)\n"
        "features_testing = assembler.transform(testing_df)\n"
        "features_evaluation = assembler.transform(testing_df)\n"
    )
    previous = os.environ.get("LO_WRITE_OVERLAP")
    os.environ["LO_WRITE_OVERLAP"] = overlap
    try:
        return build_model(
            store,
            "train",
            "test",
            preprocessor,
            list(classifiers),
        )
    finally:
        if previous is None:
            os.environ.pop("LO_WRITE_OVERLAP", None)
        else:
            os.environ["LO_WRITE_OVERLAP"] = previous


def _seed_build_dataset(store):
    rng = np.random.default_rng(3)
    X = rng.random((80, 4))
    y = (X[:, 0] > 0.5).astype(float)
    for name in ("train", "test"):
        store.create_collection(name)
        store.insert_one(
            name, {ROW_ID: 0, "filename": name, "finished": True}
        )
        columns = {f"f{i}": X[:, i].tolist() for i in range(4)}
        columns["label"] = y.tolist()
        store.insert_columns(name, columns)


class TestOverlappedWriteBack:
    def test_overlapped_matches_synchronous(self):
        store_sync = InMemoryStore()
        _seed_build_dataset(store_sync)
        results_sync = _build_tiny(store_sync, overlap="0")

        store_async = InMemoryStore()
        _seed_build_dataset(store_async)
        results_async = _build_tiny(store_async, overlap="1")

        for sync_md, async_md in zip(results_sync, results_async):
            name = sync_md["classificator"]
            assert async_md["classificator"] == name
            assert async_md["accuracy"] == sync_md["accuracy"]
            # the barrier ran: timings are complete, write included
            assert "write" in async_md["timings"]
            out = f"test_prediction_{name}"
            sync_rows = store_sync.read_columns(out, ["prediction"])
            async_rows = store_async.read_columns(out, ["prediction"])
            assert sync_rows == async_rows
            # metadata document landed after the rows
            assert store_async.find_one(out, {ROW_ID: 0})["timings"]

    def test_write_failure_fails_the_build(self):
        class FailingWrites(InMemoryStore):
            def insert_columns(self, collection, columns, start_id=None):
                if "_prediction_" in collection:
                    raise RuntimeError("store full (injected)")
                super().insert_columns(collection, columns, start_id)

        store = FailingWrites()
        _seed_build_dataset(store)
        with pytest.raises(RuntimeError, match="store full"):
            _build_tiny(store, overlap="1", classifiers=("nb",))


class TestKnobPlumbing:
    def test_capacity_env_validation(self, monkeypatch):
        monkeypatch.setenv("LO_DEVCACHE_BYTES", "2e9")
        assert devcache.capacity_bytes() == 2_000_000_000
        monkeypatch.setenv("LO_DEVCACHE_BYTES", "0")
        assert devcache.capacity_bytes() == 0
        for bad in ("lots", "-1"):
            monkeypatch.setenv("LO_DEVCACHE_BYTES", bad)
            with pytest.raises(ValueError):
                devcache.capacity_bytes()

    def test_cluster_manifest_dataplane_section(self, tmp_path):
        import json
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
                    "dataplane": {
                        "devcache_bytes": 123456,
                        "store_compress": 1,
                        "write_overlap": 0,
                    },
                }
            )
        )
        loaded = cluster.load_manifest(str(path))
        env = cluster.machine_plans(loaded)[0]["env"]
        assert env["LO_DEVCACHE_BYTES"] == "123456"
        assert env["LO_STORE_COMPRESS"] == "1"
        assert env["LO_WRITE_OVERLAP"] == "0"
        bad = tmp_path / "bad.json"
        for section in (
            {"devcache_bytes": -1},
            {"devcache_bytes": True},  # bool is an int subclass
            {"store_compress": 2},
            {"write_overlap": "1"},
            {"mystery_knob": 1},
        ):
            bad.write_text(
                json.dumps(
                    {
                        "repo": ".",
                        "head": {"host": "127.0.0.1"},
                        "dataplane": section,
                    }
                )
            )
            with pytest.raises(SystemExit):
                cluster.load_manifest(str(bad))


class TestBuilderCachedLoads:
    def test_second_build_skips_the_read(self):
        store = InMemoryStore()
        _seed_build_dataset(store)
        _build_tiny(store, overlap="1", classifiers=("nb",))
        stats_first = devcache.global_devcache().stats()
        _build_tiny(store, overlap="1", classifiers=("nb",))
        stats_second = devcache.global_devcache().stats()
        # warm build: train+test table reads hit; no new loads for them
        assert stats_second["hits"] >= stats_first["hits"] + 2
