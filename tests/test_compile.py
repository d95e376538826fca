"""AOT compile plane (docs/compile.md): manifest enumeration, the
boot precompile pass, executable serialization, the fleet cache's
trust boundary, and the restart drill.

The load-bearing claims, each pinned here:

- the program universe is finite, deterministic, and capped LOUDLY
  (dropped specs are returned and logged, never silently absent);
- an AOT compile writes the SAME persistent-cache entry the request
  path would read (compile → recompile is a cache hit);
- a serialized executable round-trips bit-identically;
- the fleet cache discards version-mismatched or corrupt artifacts
  WITHOUT deserializing them, and a half-published artifact (chunks,
  no meta row) is invisible;
- a runner restarted after kill -9 with an EMPTY local cache replays
  the published programs with ZERO compile misses (the whole plane's
  contract, end to end across real processes).
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from learningorchestra_tpu import compile as lo_compile
from learningorchestra_tpu.compile import config as compile_config
from learningorchestra_tpu.compile import fleetcache
from learningorchestra_tpu.compile.manifest import (
    ProgramSpec,
    enumerate_programs,
    lr_segment_iters,
    serve_row_buckets,
    specs_for_artifact,
)
from learningorchestra_tpu.utils import jitcache


@pytest.fixture()
def mesh():
    from learningorchestra_tpu.ml.base import resolve_mesh

    return resolve_mesh(None)


@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    """Point JAX's persistent compilation cache at a per-test dir.

    Bypasses enable_compile_cache()'s first-dir-wins global so tests
    stay hermetic, but applies the same config the product applies —
    including the xla-caches off switch that keeps keys portable."""
    import jax
    from jax._src import compilation_cache

    d = str(tmp_path / "jit_cache")
    os.makedirs(d, exist_ok=True)
    old_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "")
    # earlier compiles in this process may have initialized (or
    # memoized away) the cache under the previous dir — start over
    compilation_cache.reset_cache()
    jitcache._register_listeners()
    monkeypatch.setattr(jitcache, "_ACTIVE_DIR", d)
    yield d
    jax.config.update("jax_compilation_cache_dir", old_dir)
    compilation_cache.reset_cache()


class TestConfig:
    def test_defaults(self, monkeypatch):
        for name in ("LO_AOT", "LO_AOT_MAX_PROGRAMS", "LO_AOT_PUBLISH"):
            monkeypatch.delenv(name, raising=False)
        assert compile_config.validate_env() == {
            "LO_AOT": 0,
            "LO_AOT_MAX_PROGRAMS": 64,
            "LO_AOT_PUBLISH": 1,
        }

    def test_happy_path(self, monkeypatch):
        monkeypatch.setenv("LO_AOT", "1")
        monkeypatch.setenv("LO_AOT_MAX_PROGRAMS", "0")
        monkeypatch.setenv("LO_AOT_PUBLISH", "0")
        assert compile_config.validate_env() == {
            "LO_AOT": 1,
            "LO_AOT_MAX_PROGRAMS": 0,
            "LO_AOT_PUBLISH": 0,
        }

    @pytest.mark.parametrize("value", ["2", "yes", "true", "1.0"])
    def test_bad_flag_rejected(self, monkeypatch, value):
        monkeypatch.setenv("LO_AOT", value)
        with pytest.raises(ValueError):
            compile_config.validate_env()

    @pytest.mark.parametrize("value", ["64.0", "-1", "many"])
    def test_bad_max_programs_rejected(self, monkeypatch, value):
        monkeypatch.setenv("LO_AOT_MAX_PROGRAMS", value)
        with pytest.raises(ValueError):
            compile_config.validate_env()

    @pytest.mark.parametrize("value", ["2", "on"])
    def test_bad_publish_rejected(self, monkeypatch, value):
        monkeypatch.setenv("LO_AOT_PUBLISH", value)
        with pytest.raises(ValueError):
            compile_config.validate_env()


class TestManifest:
    def test_universe_covers_every_program_family(self, mesh):
        kept, dropped = enumerate_programs(mesh, max_programs=10_000)
        assert not dropped
        families = {spec.program for spec in kept}
        assert families >= {
            "predict:lr", "predict:nb", "predict:dt", "predict:rf",
            "predict:gb", "build:lr", "build:nb", "sweep:lr",
        }

    def test_keys_unique_and_deterministic(self, mesh):
        kept, _ = enumerate_programs(mesh, max_programs=10_000)
        keys = [spec.key for spec in kept]
        assert len(keys) == len(set(keys))
        again, _ = enumerate_programs(mesh, max_programs=10_000)
        assert [s.key for s in again] == keys  # fleet-wide agreement

    def test_cap_returns_the_drop_list(self, mesh):
        full, _ = enumerate_programs(mesh, max_programs=10_000)
        kept, dropped = enumerate_programs(mesh, max_programs=3)
        assert len(kept) == 3
        # nothing silently vanishes: kept + dropped IS the universe
        assert [s.key for s in kept + dropped] == [s.key for s in full]
        # predicts sort first: cheapest compiles, costliest to miss
        assert all(s.program.startswith("predict:") for s in kept)

    def test_cap_zero_keeps_nothing(self, mesh):
        kept, dropped = enumerate_programs(mesh, max_programs=0)
        assert kept == [] and len(dropped) > 0

    def test_serve_buckets_collapse_to_fixed_dispatch_shape(self, mesh):
        # the batcher pads every request to grid_size(total, max_batch)
        # with floor=max_batch — ONE compiled predict program per model
        assert len(serve_row_buckets(mesh, max_batch=64)) == 1

    def test_lr_segment_iters_divides_the_budget(self):
        iters = lr_segment_iters(rows=64, features=8, max_iter=100)
        assert isinstance(iters, int) and iters >= 1
        assert 100 % iters == 0  # segments replay the exact fit chain

    def test_specs_for_artifact_reads_checkpoint_shapes(
        self, mesh, tmp_path
    ):
        from learningorchestra_tpu.ml.base import make_classifier
        from learningorchestra_tpu.ml.checkpoint import save_model

        rng = np.random.default_rng(0)
        X = rng.random((32, 5)).astype(np.float32)
        y = (X[:, 0] > 0.5).astype(np.int64)
        model = make_classifier("lr").fit(X, y)
        path = str(tmp_path / "m.model")
        save_model(model, path)
        specs = specs_for_artifact(path, mesh)
        assert specs and all(s.program == "predict:lr" for s in specs)
        assert all(s.features == 5 and s.num_classes == 2 for s in specs)


def _predict_spec(mesh) -> ProgramSpec:
    kept, _ = enumerate_programs(mesh, max_programs=10_000)
    return next(s for s in kept if s.program == "predict:lr")


class TestCompileSpec:
    def test_aot_compile_writes_then_hits_the_persistent_cache(
        self, mesh, cache_dir
    ):
        from learningorchestra_tpu.compile.aot import compile_spec

        spec = _predict_spec(mesh)
        before = jitcache.raw_stats()
        compile_spec(spec)
        assert os.listdir(cache_dir)  # the entry the fleet cache ships
        mid = jitcache.raw_stats()
        assert (
            mid["persistent_cache_misses"]
            == before["persistent_cache_misses"] + 1
        )
        # a recompile of the same spec never re-enters the compiler:
        # in-process jax satisfies it from memory (no second miss); the
        # cross-PROCESS cache load is TestRestartDrill's assertion
        compile_spec(spec)
        after = jitcache.raw_stats()
        assert (
            after["persistent_cache_misses"]
            == mid["persistent_cache_misses"]
        )

    def test_compile_source_attribution_scopes_and_restores(self):
        assert jitcache._COMPILE_SOURCE.get() == ("jit", None)
        with jitcache.compile_source("aot", "k1"):
            assert jitcache._COMPILE_SOURCE.get() == ("aot", "k1")
            with jitcache.compile_source("fleetcache"):
                assert jitcache._COMPILE_SOURCE.get() == (
                    "fleetcache",
                    None,
                )
            assert jitcache._COMPILE_SOURCE.get() == ("aot", "k1")
        assert jitcache._COMPILE_SOURCE.get() == ("jit", None)


class TestSerializeRoundTrip:
    def test_serialized_executable_executes_bit_identically(
        self, mesh, cache_dir
    ):
        import jax

        from learningorchestra_tpu.compile.aot import (
            compile_spec,
            deserialize_compiled,
            serialize_compiled,
        )
        from learningorchestra_tpu.compile.manifest import lower_args

        spec = _predict_spec(mesh)
        compiled = compile_spec(spec)
        blob = serialize_compiled(compiled)
        restored = deserialize_compiled(blob)
        _, args, _ = lower_args(spec)
        rng = np.random.default_rng(7)
        concrete = jax.tree.map(
            lambda s: (rng.random(s.shape) + 0.5).astype(s.dtype), args
        )
        want = compiled(*concrete)
        got = restored(*concrete)
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))

    def test_corrupt_blob_raises_for_the_caller_to_discard(self):
        from learningorchestra_tpu.compile.aot import deserialize_compiled

        with pytest.raises(Exception):
            deserialize_compiled(b"not a pickled executable")


def _write_fake_entries(cache_dir: str, n: int = 3) -> dict:
    out = {}
    for i in range(n):
        name = f"jit_fake-{i}-cache"
        data = os.urandom(4096 + i)
        with open(os.path.join(cache_dir, name), "wb") as handle:
            handle.write(data)
        out[name] = data
    return out


class TestFleetCache:
    def test_publish_fetch_round_trip_byte_identity(self, store, tmp_path):
        src = str(tmp_path / "src")
        dst = str(tmp_path / "dst")
        os.makedirs(src)
        os.makedirs(dst)
        files = _write_fake_entries(src)
        stats = fleetcache.publish(store, src)
        assert stats["published"] == len(files)
        fetched = fleetcache.fetch(store, dst)
        assert fetched["fetched"] == len(files)
        for name, data in files.items():
            with open(os.path.join(dst, name), "rb") as handle:
                assert handle.read() == data

    def test_republish_skips_already_published(self, store, tmp_path):
        src = str(tmp_path / "src")
        os.makedirs(src)
        _write_fake_entries(src)
        fleetcache.publish(store, src)
        again = fleetcache.publish(store, src)
        assert again == {"published": 0, "skipped": 3}

    def test_rev_guard_makes_refetch_a_noop(self, store, tmp_path):
        src, dst = str(tmp_path / "src"), str(tmp_path / "dst")
        os.makedirs(src)
        os.makedirs(dst)
        _write_fake_entries(src)
        fleetcache.publish(store, src)
        assert fleetcache.fetch(store, dst)["fetched"] == 3
        assert fleetcache.fetch(store, dst) == {
            "fetched": 0,
            "discarded": 0,
            "skipped": 0,
        }

    def test_version_mismatch_discarded_without_decode(
        self, store, tmp_path, monkeypatch
    ):
        src, dst = str(tmp_path / "src"), str(tmp_path / "dst")
        os.makedirs(src)
        os.makedirs(dst)
        _write_fake_entries(src, n=1)
        monkeypatch.setattr(
            fleetcache,
            "_fingerprint_json",
            lambda: json.dumps({"jaxlib": "0.0.0-other-machine"}),
        )
        fleetcache.publish(store, src)
        monkeypatch.undo()
        stats = fleetcache.fetch(store, dst)
        assert stats["fetched"] == 0 and stats["discarded"] == 1
        assert os.listdir(dst) == []  # recompile, never deserialize

    def test_corrupt_chunks_discarded(self, store, tmp_path):
        import base64

        dst = str(tmp_path / "dst")
        os.makedirs(dst)
        payload = b"executable bytes"
        store.insert_one(
            fleetcache.COLLECTION,
            {
                "artifact": "jit_x-cache",
                "seq": 0,
                "data": base64.b64encode(payload).decode(),
            },
        )
        store.insert_one(
            fleetcache.COLLECTION,
            {
                "artifact": "jit_x-cache",
                "meta": 1,
                "chunks": 1,
                "size": len(payload),
                "sha256": "0" * 64,  # wrong digest
                "fingerprint": fleetcache._fingerprint_json(),
            },
        )
        stats = fleetcache.fetch(store, dst)
        assert stats["discarded"] == 1 and os.listdir(dst) == []

    def test_half_published_artifact_is_invisible(self, store, tmp_path):
        import base64

        dst = str(tmp_path / "dst")
        os.makedirs(dst)
        # chunks landed, meta row (written LAST by publish) did not:
        # the reader must see nothing at all
        store.insert_one(
            fleetcache.COLLECTION,
            {
                "artifact": "jit_partial-cache",
                "seq": 0,
                "data": base64.b64encode(b"half").decode(),
            },
        )
        stats = fleetcache.fetch(store, dst)
        assert stats == {"fetched": 0, "discarded": 0, "skipped": 0}
        assert os.listdir(dst) == []

    def test_path_traversal_artifact_rejected(self, store, tmp_path):
        import base64

        dst = str(tmp_path / "dst")
        os.makedirs(dst)
        evil = os.path.join("..", "evil-cache")
        payload = b"nope"
        store.insert_one(
            fleetcache.COLLECTION,
            {
                "artifact": evil,
                "seq": 0,
                "data": base64.b64encode(payload).decode(),
            },
        )
        store.insert_one(
            fleetcache.COLLECTION,
            {
                "artifact": evil,
                "meta": 1,
                "chunks": 1,
                "size": len(payload),
                "sha256": hashlib.sha256(payload).hexdigest(),
                "fingerprint": fleetcache._fingerprint_json(),
            },
        )
        stats = fleetcache.fetch(store, dst)
        assert stats["fetched"] == 0
        assert not os.path.exists(str(tmp_path / "evil-cache"))


class TestWarmup:
    def test_lr_warmup_derives_width_and_executes(self, tmp_path):
        from learningorchestra_tpu.compile.warmup import warm_artifact
        from learningorchestra_tpu.ml.base import make_classifier
        from learningorchestra_tpu.ml.checkpoint import save_model

        rng = np.random.default_rng(1)
        X = rng.random((32, 6)).astype(np.float32)
        y = (X[:, 0] > 0.5).astype(np.int64)
        path = str(tmp_path / "warm.model")
        save_model(make_classifier("lr").fit(X, y), path)
        assert warm_artifact(path) is True

    def test_tree_warmup_without_width_skips_honestly(self, tmp_path):
        from learningorchestra_tpu.compile.warmup import warm_artifact
        from learningorchestra_tpu.ml.base import make_classifier
        from learningorchestra_tpu.ml.checkpoint import save_model

        rng = np.random.default_rng(2)
        X = rng.random((32, 4)).astype(np.float32)
        y = (X[:, 0] > 0.5).astype(np.int64)
        path = str(tmp_path / "tree.model")
        save_model(make_classifier("dt").fit(X, y), path)
        # tree checkpoints don't record feature width: a guessed-width
        # warmup would compile a program serving never dispatches
        assert warm_artifact(path) is False


class TestPublishHook:
    def test_handler_registration_returns_previous(self):
        calls = []
        old = lo_compile.set_publish_handler(
            lambda path, features: calls.append((path, features))
        )
        try:
            lo_compile.checkpoint_published("/models/a.model", 7)
            assert calls == [("/models/a.model", 7)]
        finally:
            lo_compile.set_publish_handler(old)

    def test_raising_handler_never_fails_the_publication(self):
        def boom(path, features):
            raise RuntimeError("warmup exploded")

        old = lo_compile.set_publish_handler(boom)
        try:
            lo_compile.checkpoint_published("/models/b.model")
        finally:
            lo_compile.set_publish_handler(old)

    def test_default_is_a_noop(self):
        old = lo_compile.set_publish_handler(None)
        try:
            lo_compile.checkpoint_published("/models/c.model")
        finally:
            lo_compile.set_publish_handler(old)


_DRILL_CHILD = textwrap.dedent(
    """
    import hashlib, json, os, sys

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from learningorchestra_tpu.utils import jitcache
    from learningorchestra_tpu.compile.aot import AotPlane
    from learningorchestra_tpu.compile.manifest import (
        enumerate_programs, lower_args,
    )
    from learningorchestra_tpu.core.store_service import RemoteStore
    from learningorchestra_tpu.ml.base import resolve_mesh

    # the parent placed the cache through JAX_COMPILATION_CACHE_DIR
    cache_dir = jitcache.enable_compile_cache()
    store = RemoteStore(os.environ["DRILL_STORE_URL"])
    plane = AotPlane(
        store=store, cache_dir=cache_dir,
        max_programs=int(os.environ["DRILL_MAX_PROGRAMS"]),
    )
    stats = plane.run()
    # execute the first predict program on a fixed input and report a
    # digest: the restarted runner must produce the SAME bits
    mesh = resolve_mesh(None)
    kept, _ = enumerate_programs(
        mesh, max_programs=int(os.environ["DRILL_MAX_PROGRAMS"])
    )
    spec = next(s for s in kept if s.program.startswith("predict:"))
    fn, args, statics = lower_args(spec)
    rng = np.random.default_rng(3)
    concrete = jax.tree.map(
        lambda s: (rng.random(s.shape) + 0.5).astype(s.dtype), args
    )
    out = fn.lower(*concrete, **statics).compile()(*concrete)
    digest = hashlib.sha256(
        b"".join(np.asarray(leaf).tobytes() for leaf in jax.tree.leaves(out))
    ).hexdigest()
    print(json.dumps({
        "stats": stats,
        "digest": digest,
        "raw": jitcache.raw_stats(),
    }), flush=True)
    if os.environ.get("DRILL_SELF_KILL") == "1":
        sys.stdout.flush()
        os.kill(os.getpid(), 9)  # the crash the fleet cache outlives
    """
)


def _run_drill_child(cache_dir, store_url, max_programs, self_kill):
    env = dict(
        os.environ,
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        DRILL_STORE_URL=store_url,
        DRILL_MAX_PROGRAMS=str(max_programs),
        DRILL_SELF_KILL="1" if self_kill else "0",
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, "-c", _DRILL_CHILD],
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    lines = [
        line for line in proc.stdout.splitlines() if line.startswith("{")
    ]
    if not lines:
        raise AssertionError(
            f"drill child produced no record (rc={proc.returncode}): "
            f"{proc.stderr[-800:]}"
        )
    return json.loads(lines[-1]), proc.returncode


class TestRestartDrill:
    def test_restarted_runner_pays_zero_compile_misses(self, tmp_path):
        """kill -9 a runner that compiled + published the grid; a
        restarted runner with an EMPTY local cache fetches the fleet's
        executables and replays every published program with ZERO
        persistent-cache misses — and bit-identical outputs."""
        from learningorchestra_tpu.core.store import InMemoryStore
        from learningorchestra_tpu.core.store_service import create_store_app
        from learningorchestra_tpu.utils.web import ServerThread

        store = InMemoryStore()
        server = ServerThread(
            create_store_app(store), "127.0.0.1", 0
        ).start()
        url = f"http://127.0.0.1:{server.port}"
        first_dir = str(tmp_path / "first")
        restart_dir = str(tmp_path / "restart")
        os.makedirs(first_dir)
        os.makedirs(restart_dir)
        try:
            first, rc = _run_drill_child(
                first_dir, url, max_programs=2, self_kill=True
            )
            assert rc == -9  # it really died mid-flight
            assert first["stats"]["compiled"] == 2
            assert first["stats"]["published"] > 0
            assert store.find(fleetcache.COLLECTION, {"meta": 1})

            restarted, rc = _run_drill_child(
                restart_dir, url, max_programs=2, self_kill=False
            )
            assert rc == 0
            assert restarted["stats"]["fetched"] > 0
            # THE contract: every published program came off the wire
            assert restarted["raw"]["persistent_cache_misses"] == 0
            assert restarted["raw"]["persistent_cache_hits"] >= 2
            assert restarted["digest"] == first["digest"]
        finally:
            server.stop()
