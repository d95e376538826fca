"""AOT compiler: lower + compile the program manifest ahead of demand.

The mechanism is the persistent XLA compilation cache
(utils/jitcache.py): ``jit(fn).lower(...).compile()`` writes the same
serialized-executable cache entry a request-path jit dispatch would,
so a boot-time pass over the manifest (compile/manifest.py) turns
every first-request compile into a cache load — measured here at ~3 ms
versus ~46 ms for even the smallest real compile, and two orders more
for tree fits. Where the installed jax additionally supports direct
executable serialization (``jax.experimental.serialize_executable``),
:func:`serialize_compiled` / :func:`deserialize_compiled` round-trip a
``Compiled`` handle in-process — the bit-identity contract the tests
pin; when it doesn't, the plane falls back cleanly to cache warming
alone.

Keying follows the devcache discipline: an artifact is only trusted
under the exact (jax, jaxlib, backend platform + version) fingerprint
that produced it (:func:`backend_fingerprint`) — the fleet cache
(compile/fleetcache.py) discards on mismatch WITHOUT deserializing,
never loads wrong.

The pass runs off the device queue's hot lane: a plain daemon thread
(compilation is host CPU work — it never occupies a device-class
scheduler slot), every compile attributed to its manifest key via
``jitcache.compile_source`` so the flight recorder separates boot
compiles from request-path stalls.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

from learningorchestra_tpu.compile import config as compile_config
from learningorchestra_tpu.compile.manifest import (
    ProgramSpec,
    enumerate_programs,
    lower_args,
    specs_for_artifact,
)

_METRICS = None
_METRICS_LOCK = threading.Lock()


def _aot_metrics() -> dict:
    global _METRICS
    with _METRICS_LOCK:
        if _METRICS is None:
            from learningorchestra_tpu.telemetry.metrics import (
                global_registry,
            )

            registry = global_registry()
            _METRICS = {
                "compiled": registry.counter(
                    "lo_aot_programs_compiled_total",
                    "Manifest programs compiled by the AOT pass",
                ),
                "published": registry.counter(
                    "lo_aot_programs_published_total",
                    "Executable artifacts published to the fleet cache",
                ),
                "fetched": registry.counter(
                    "lo_aot_programs_fetched_total",
                    "Executable artifacts pulled from the fleet cache",
                ),
                "discarded": registry.counter(
                    "lo_aot_programs_discarded_total",
                    "Fleet artifacts dropped (version-fingerprint "
                    "mismatch or corrupt payload) and recompiled",
                ),
            }
        return _METRICS


def backend_fingerprint() -> dict:
    """The version envelope an executable artifact is only valid under
    — same role as the devcache key's dtype/mesh components: a
    fingerprint mismatch means "recompile", never "deserialize and
    hope". Platform version covers the XLA build; jax/jaxlib cover
    the tracing + serialization format."""
    import jax
    import jaxlib.version

    device = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.version.__version__,
        "platform": jax.default_backend(),
        "platform_version": str(
            getattr(device.client, "platform_version", "")
        ),
    }


@contextlib.contextmanager
def persist_all_compiles():
    """Drop the persistent cache's admission thresholds for the block.

    The defaults (min compile time 1 s) exist to keep request-path
    trivia out of the cache — but the AOT pass compiles exactly the
    programs the fleet WILL dispatch, and a sub-second serve forward
    skipped at boot is precisely the compile the first predict would
    then eat. Process-global config: a concurrent request compile also
    persisting during the window is harmless (same cache, same keys)."""
    import jax

    old_time = jax.config.jax_persistent_cache_min_compile_time_secs
    old_size = jax.config.jax_persistent_cache_min_entry_size_bytes
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        yield
    finally:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", old_time
        )
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", old_size
        )


def compile_spec(spec: ProgramSpec, source: str = "aot"):
    """Lower + compile one manifest entry, attributed to its manifest
    key in the flight recorder. Returns the ``Compiled`` handle (the
    persistent-cache write is the side effect the plane exists for),
    or raises whatever the lowering raised — the caller decides
    whether a spec failure is fatal (the background pass logs and
    continues; tests assert)."""
    from learningorchestra_tpu.utils import jitcache

    fn, args, statics = lower_args(spec)
    with jitcache.compile_source(source, spec.key):
        with persist_all_compiles():
            compiled = fn.lower(*args, **statics).compile()
    _aot_metrics()["compiled"].inc()
    return compiled


def serialize_compiled(compiled) -> bytes:
    """One self-contained payload for a ``Compiled`` handle (executable
    bytes + arg/result pytree defs, pickled together)."""
    import pickle

    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree))


def deserialize_compiled(blob: bytes):
    """Load a :func:`serialize_compiled` payload back into a callable
    executable. Only valid under the same :func:`backend_fingerprint`
    that serialized it — the fleet cache enforces that BEFORE this
    runs; corrupt payloads raise (callers discard and recompile)."""
    import pickle

    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = pickle.loads(blob)
    return serialize_executable.deserialize_and_load(
        payload, in_tree, out_tree
    )


class AotPlane:
    """The boot-time precompile pass, runnable synchronously (tests,
    scripts) or as a background daemon thread (the runner).

    One pass: fleet-fetch serialized artifacts into the local cache
    dir → enumerate the manifest (+ exact specs for every published
    checkpoint in ``models_dir``) → compile everything under the cap
    (dropped entries are LOGGED, satisfying the no-silent-caps
    contract) → publish fresh cache entries back to the fleet."""

    def __init__(
        self,
        mesh=None,
        store=None,
        models_dir: str = "",
        cache_dir: Optional[str] = None,
        max_programs: Optional[int] = None,
        publish: Optional[bool] = None,
    ):
        self.mesh = mesh
        self.store = store
        self.models_dir = models_dir
        self.cache_dir = cache_dir
        self.max_programs = (
            compile_config.max_programs()
            if max_programs is None
            else max_programs
        )
        self.publish = (
            compile_config.publish_enabled() if publish is None else publish
        )
        self._stats: dict = {"state": "idle"}
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    def stats(self) -> dict:
        with self._lock:
            return dict(self._stats)

    def _specs(self) -> tuple[list[ProgramSpec], list[ProgramSpec]]:
        import os

        from learningorchestra_tpu.ml.base import resolve_mesh
        from learningorchestra_tpu.ml.checkpoint import CHECKPOINT_SUFFIX

        mesh = self.mesh = resolve_mesh(self.mesh)
        specs, _ = enumerate_programs(mesh)
        seen = {s.key for s in specs}
        if self.models_dir and os.path.isdir(self.models_dir):
            for entry in sorted(os.listdir(self.models_dir)):
                if not entry.endswith(CHECKPOINT_SUFFIX):
                    continue
                try:
                    derived = specs_for_artifact(
                        os.path.join(self.models_dir, entry), mesh
                    )
                except Exception:  # corrupt checkpoint: not this plane's
                    continue      # problem — the serve path 500s it
                for spec in derived:
                    if spec.key not in seen:
                        seen.add(spec.key)
                        specs.append(spec)
        return specs[: self.max_programs], specs[self.max_programs:]

    def run(self) -> dict:
        """The synchronous pass; returns (and retains, for
        /debug-style introspection) its stats dict."""
        import time

        from learningorchestra_tpu.compile import fleetcache
        from learningorchestra_tpu.utils import jitcache

        started = time.perf_counter()
        stats: dict = {
            "state": "running", "compiled": 0, "failed": 0,
            "fetched": 0, "discarded": 0, "published": 0, "dropped": 0,
        }
        # published ONCE: stats() snapshots this same dict under the
        # lock, so progress is visible live and there is no second
        # assignment for a reader to race between
        with self._lock:
            self._stats = stats
        cache_dir = self.cache_dir or jitcache.enable_compile_cache()
        source = "aot"
        if self.store is not None and cache_dir:
            fetch_stats = fleetcache.fetch(self.store, cache_dir)
            stats["fetched"] = fetch_stats["fetched"]
            stats["discarded"] = fetch_stats["discarded"]
            if fetch_stats["fetched"]:
                # warm pass over fleet-fetched artifacts: compiles now
                # resolve as cache loads and the recorder should say
                # the fleet (not this process's compiler) paid for them
                source = "fleetcache"
        kept, dropped = self._specs()
        stats["dropped"] = len(dropped)
        if dropped:
            # no silent caps: name what the cap excluded
            print(
                f"[aot] LO_AOT_MAX_PROGRAMS={self.max_programs} dropped "
                f"{len(dropped)} programs: "
                + ", ".join(s.key for s in dropped[:8])
                + ("..." if len(dropped) > 8 else ""),
                flush=True,
            )
        for spec in kept:
            try:
                compile_spec(spec, source=source)
                stats["compiled"] += 1
            except Exception as error:  # noqa: BLE001 — pass is advisory
                stats["failed"] += 1
                print(f"[aot] {spec.key} failed: {error}", flush=True)
        if self.store is not None and cache_dir and self.publish:
            publish_stats = fleetcache.publish(self.store, cache_dir)
            stats["published"] = publish_stats["published"]
        stats["seconds"] = round(time.perf_counter() - started, 3)
        stats["state"] = "done"
        return stats

    def start(self) -> "AotPlane":
        """Run the pass on a background daemon thread — boot returns
        immediately; the thread never holds a device-class slot."""
        thread = threading.Thread(
            target=self.run, name="lo-aot-precompile", daemon=True
        )
        self._thread = thread
        thread.start()
        return self

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)


def boot_compile_plane(
    store=None, models_dir: str = "", cache_dir: Optional[str] = None
) -> Optional[AotPlane]:
    """The runner's boot hook: start the background precompile pass
    when ``LO_AOT=1``, else do nothing (the knob is validated either
    way — a typo'd LO_AOT refuses bring-up upstream in the preflight)."""
    if not compile_config.aot_enabled():
        return None
    return AotPlane(
        store=store, models_dir=models_dir, cache_dir=cache_dir
    ).start()
