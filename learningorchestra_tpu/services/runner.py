"""Serve the service stack: all seven in one process, or one per process.

The reference deploys seven Flask containers wired to a shared MongoDB
(docker-compose.yml:173-330). Both topologies exist here:

- **single process** (default): seven WSGI servers over one in-process
  WAL-backed store — ``python -m learningorchestra_tpu.services.runner``.
- **one service per process** (the reference's microservice shape):
  set ``LO_STORE_URL`` to a store server
  (``python -m learningorchestra_tpu.core.store_service``) and launch
  each service with ``LO_SERVICE=<name>`` — every process talks to the
  shared store over its wire protocol, exactly as the reference
  containers share Mongo via ``DATABASE_URL``.

Environment:
- ``LO_SERVICE`` — serve only this service (``database_api``,
  ``projection``, ``model_builder``, ``data_type_handler``,
  ``histogram``, ``tsne``, ``pca``); unset = all seven
- ``LO_PORT`` — bind port for single-service mode (default: the
  service's reference port; ``0`` = OS-assigned, printed on stdout)
- ``LO_STORE_URL`` — store server base URL (the reference's
  ``DATABASE_URL`` analogue); unset = in-process store
- ``LO_DATA_DIR`` — store WAL directory for the in-process store
  (default ``./lo_data``)
- ``LO_IMAGES_DIR`` — PNG volume root (default ``<data>/images``)
- ``LO_MODELS_DIR`` — model checkpoint volume (default
  ``<data>/models``; empty string disables checkpointing). In
  multi-host mode this must be a volume shared by every host.
- ``LO_COORDINATOR`` / ``LO_NUM_PROCESSES`` / ``LO_PROCESS_ID`` —
  join a multi-host device runtime (parallel/multihost.py): process 0
  serves REST and broadcasts compute jobs, the rest run SPMD worker
  loops (parallel/spmd.py). Requires ``LO_STORE_URL`` and a shared
  ``LO_MODELS_DIR``. One jax process per host.
- ``LO_JOB_WORKERS`` / ``LO_SCHED_DEVICE_WIDTH`` / ``LO_SCHED_QUEUE_CAP``
  — scheduler knobs (sched/config.py has the full table): host-class
  concurrency width (default 8, replacing the old hardcoded pool),
  device-class width (default 1 — SPMD dispatches never contend for the
  mesh), and the per-class queue cap past which submissions get HTTP
  429 + ``Retry-After``. All seven services submit through ONE
  process-wide scheduler whose journal (in the store) lets a restarted
  process re-enqueue never-started jobs and terminate pollers of
  orphaned ones — docs/scheduler.md.
- ``LO_HOST`` — bind host. Defaults to ``127.0.0.1``: the model-builder
  service executes request-supplied preprocessor code (reference parity),
  so exposing the stack beyond localhost must be an explicit opt-in
  (``LO_HOST=0.0.0.0``) behind whatever sandboxing the deployment adds —
  see deploy/README.md.
- ``LO_BUILD_WORKERS`` — cap the model builder's thread-per-classifier
  fan-out (ml/builder.py). N concurrent fits hold N device working sets;
  past ~1M rows/classifier on one chip set 1 to stay inside HBM.
- ``LO_PROGRAM_ROW_STEPS`` — scale the per-program row*steps budget that
  segments long fits into short XLA executions (ml/base.segment_steps);
  larger segments mean fewer dispatches and coarser crash-resume points.
- ``JAX_COMPILATION_CACHE_DIR`` — JAX's own variable places the
  persistent XLA compilation cache; unset, every entry point uses one
  fixed ``<checkout>/.jit_cache`` (utils/jitcache.py). Shared safely
  between processes; turns per-process estimator compiles into cache
  loads.
- ``LO_SHAPE_BUCKETS`` — ``0`` disables the quarter-octave padded-shape
  grid (parallel/sharding.bucket_rows); default on, so nearby dataset
  sizes reuse one compiled program per estimator.
- ``LO_SPILL_BYTES`` / ``LO_SPILL_DIR`` — out-of-core column budget for
  the in-process store (core/store.py): past the budget, cold column
  payloads move to disk-backed mappings. Applies to the store SERVER
  process in the microservice topology.
- ``LO_DEVCACHE_BYTES`` / ``LO_STORE_COMPRESS`` / ``LO_WRITE_OVERLAP``
  — data-plane knobs (docs/dataplane.md): the rev-keyed device cache's
  capacity (core/devcache.py; 0 disables), zlib compression on the
  binary store wire, and the builder's overlapped prediction
  write-back (0 restores synchronous writes).
- ``LO_SERVE_BYTES`` / ``LO_SERVE_BATCH_WINDOW_MS`` / ``LO_SERVE_MAX_BATCH``
  / ``LO_SERVE_MAX_ROWS`` / ``LO_SERVE_QUEUE_CAP`` / ``LO_SERVE_TIMEOUT_S``
  — online-serving knobs (docs/serving.md): the model registry's
  pinned-parameter byte budget (0 = host-only fallback), the
  micro-batch collection window, the per-dispatch request cap, the
  per-request row cap (413 past it), the bounded batcher inbox (429 +
  Retry-After past it), and the per-request wait bound.
- ``LO_FLEET_REPLICAS`` / ``LO_FLEET_RF`` / ``LO_FLEET_MODEL_QPS`` /
  ``LO_FLEET_DOWN_S`` — the replicated serving fleet (docs/serving.md
  "Fleet"): replica count, owners per model on the consistent-hash
  placement ring, the router's per-model admission quota, and the
  heartbeat age past which a replica is routed around. A replica
  process additionally carries ``LO_FLEET_REPLICA=<index>`` (set by
  the supervisor — deploy/stack.py — not by operators), which arms the
  per-process :class:`~learningorchestra_tpu.serve.fleet.ReplicaAgent`;
  the router itself is ``LO_SERVICE=router`` (default port 5007).
- ``LO_COALESCE_WINDOW_MS`` / ``LO_COALESCE_MAX_JOBS`` — the job
  coalescer (docs/scheduler.md): shape-compatible device jobs arriving
  within the window fuse into ONE vmap-across-jobs dispatch (0 =
  passthrough); max_jobs caps a fused batch's job axis.
- ``LO_INGEST_SLAB_BYTES`` — CSVs past this size parse as bounded slabs
  (core/ingest.py), keeping ingest's transient working set slab-sized.
- ``LO_AUTO_PROMOTE_S`` / ``LO_PEERS`` / ``LO_FAILOVER_TIMEOUT_S`` —
  store HA: follower self-promotion, term fencing, and the client-side
  re-point window (core/store_service.py; see deploy/README.md).

Observability: every service (and the store server) answers
``GET /metrics`` in Prometheus text format, and every request carries an
``X-Correlation-Id`` that threads REST → job → SPMD broadcast → phase
spans (``GET /jobs/<name>/trace``) — docs/observability.md has the
metric catalog and scrape examples.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from typing import Optional

from learningorchestra_tpu.core.jobs import JobManager
from learningorchestra_tpu.core.store import DocumentStore, InMemoryStore
from learningorchestra_tpu.sched import (
    JobJournal,
    Scheduler,
    recover_jobs,
    shard_scope,
)
from learningorchestra_tpu.services import (
    DATA_TYPE_HANDLER_PORT,
    DATABASE_API_PORT,
    HISTOGRAM_PORT,
    MODEL_BUILDER_PORT,
    PCA_PORT,
    PROJECTION_PORT,
    ROUTER_PORT,
    TSNE_PORT,
)
from learningorchestra_tpu.services import (
    data_type_handler,
    database_api,
    histogram,
    images,
    model_builder,
    projection,
)
from learningorchestra_tpu.ml.checkpoint import checkpoint_path as _ckpt
from learningorchestra_tpu.utils.web import ServerThread


# Deployment-knob readers (sched/config.py pattern): the runner's LO_*
# env reads funnel through these so the boot surface stays greppable
# and the contract analyzer (LO305) can verify the read-once
# discipline. deploy/run.sh's preflight validates the numeric domains
# before boot; unset/empty means "use the default".


def _str_env(name: str, default: str | None = None) -> str | None:
    return os.environ.get(name, default)


def _int_env(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError as error:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from error


def _flag_env(name: str, default: bool = False) -> bool:
    """Strict 0/1 flags (the domain deploy/run.sh's preflight
    enforces): unset/empty -> ``default``, else ``raw == "1"``."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    return raw == "1"


SERVICES: dict[str, int] = {
    "database_api": DATABASE_API_PORT,
    "projection": PROJECTION_PORT,
    "model_builder": MODEL_BUILDER_PORT,
    "data_type_handler": DATA_TYPE_HANDLER_PORT,
    "histogram": HISTOGRAM_PORT,
    "tsne": TSNE_PORT,
    "pca": PCA_PORT,
}


# Services whose handlers dispatch to the device. The other four are
# host-only and never initialise a JAX backend — a chip belongs to one
# process, and deploy/stack.py runs them beside the chip's owner.
DEVICE_SERVICES = ("model_builder", "tsne", "pca")

# sysexits.h EX_UNAVAILABLE: the device this process needs is held by
# another process (or absent). Restarting cannot help, so supervisors
# (deploy/stack.py) stop on it instead of applying the restart policy.
EXIT_NO_DEVICE = 69


def claim_devices() -> str:
    """Initialise the JAX backend at boot — not inside the first
    request — and describe what came up, so an operator (and
    chip_smoke.py) reads the platform this process will compute on
    instead of inferring it from ``JAX_PLATFORMS``. A backend that
    cannot initialise ends the process with :data:`EXIT_NO_DEVICE` and
    the reason: one process owns a chip, and a second owner must say so
    rather than hang a request or crash-loop under a supervisor."""
    import jax

    from learningorchestra_tpu.parallel.mesh import default_mesh

    try:
        devices = jax.devices()
    except RuntimeError as error:
        print(
            f"device unavailable: {error}\n"
            "One process owns a chip: run the single-process runner, or "
            "give each device-using service (LO_SERVICE="
            f"{'/'.join(DEVICE_SERVICES)}) its own chip.",
            flush=True,
        )
        sys.exit(EXIT_NO_DEVICE)
    shape = default_mesh().shape
    return (
        f"device: platform={jax.default_backend()} "
        f"kind={json.dumps(devices[0].device_kind)} "
        f"count={len(devices)} "
        f"mesh={'x'.join(str(size) for size in shape.values())}"
    )


def make_dispatcher(store: DocumentStore, images_dir: str):
    """SPMD dispatcher for the compute jobs (model fits, embeddings):
    the coordinator's REST handler submits, every process executes, only
    the coordinator writes to the store / images volume."""
    import jax

    from learningorchestra_tpu.ml.builder import build_model, predict_with_model
    from learningorchestra_tpu.ops.images import create_embedding_image
    from learningorchestra_tpu.parallel.spmd import SpmdDispatcher

    coordinator = jax.process_index() == 0
    dispatcher = SpmdDispatcher()

    def handle_build_model(payload: dict) -> None:
        # models_dir comes from the BROADCAST payload on every process —
        # never from per-host env — so the decision to enter the
        # checkpoint gather collective is identical across the mesh
        # (write_outputs still keeps filesystem writes coordinator-only)
        build_model(
            store,
            payload["training_filename"],
            payload["test_filename"],
            payload["preprocessor_code"],
            payload["classificators_list"],
            write_outputs=coordinator,
            models_dir=payload.get("models_dir"),
        )

    def handle_predict_model(payload: dict) -> None:
        predict_with_model(
            store,
            payload["checkpoint_path"],
            payload["training_filename"],
            payload["test_filename"],
            payload["preprocessor_code"],
            payload["prediction_filename"],
            write_outputs=coordinator,
        )

    def handle_embedding_image(payload: dict) -> None:
        create_embedding_image(
            store,
            payload["parent_filename"],
            payload["label_name"],
            payload["output_filename"],
            os.path.join(images_dir, payload["method"]),
            payload["method"],
            render=coordinator,
        )

    dispatcher.register("build_model", handle_build_model)
    dispatcher.register("predict_model", handle_predict_model)
    dispatcher.register("embedding_image", handle_embedding_image)
    return dispatcher


def make_job_manager(store: DocumentStore, scope: str = "all") -> JobManager:
    """One JobManager for the whole process: every service submits
    through a single scheduler, so the DEVICE class serializes builds
    and embeddings against each other process-wide, and every submit is
    journaled in the shared store for crash recovery."""
    return JobManager(
        # the scope gains the store's shard-topology suffix so recovery
        # replays stay shard-local (sched/journal.py shard_scope);
        # unsharded stores keep their scope strings byte-identical
        scheduler=Scheduler(
            journal=JobJournal(store, scope=shard_scope(scope, store))
        )
    )


def build_app(
    name: str,
    store: DocumentStore,
    images_dir: str,
    dispatcher=None,
    models_dir: str = "",
    jobs: "JobManager | None" = None,
):
    if name == "database_api":
        return database_api.create_app(store, jobs or JobManager())
    if name == "projection":
        return projection.create_app(store, jobs)
    if name == "model_builder":
        # Opt-in (LO_MODELS_DIR / models_dir): library and test callers
        # of start_all don't silently grow a checkpoint directory.
        models_dir = models_dir or _str_env("LO_MODELS_DIR", "")
        build = None
        predict = None
        if dispatcher is not None:
            def build(body: dict) -> None:
                payload = {
                    key: body[key]
                    for key in (
                        "training_filename",
                        "test_filename",
                        "preprocessor_code",
                        "classificators_list",
                    )
                }
                payload["models_dir"] = models_dir
                dispatcher.submit("build_model", payload)

            def predict(model_name: str, body: dict) -> None:
                dispatcher.submit(
                    "predict_model",
                    {
                        "checkpoint_path": _ckpt(models_dir, model_name),
                        "training_filename": body["training_filename"],
                        "test_filename": body["test_filename"],
                        "preprocessor_code": body["preprocessor_code"],
                        "prediction_filename": body["prediction_filename"],
                    },
                )
        return model_builder.create_app(
            store, build=build, models_dir=models_dir, predict=predict,
            jobs=jobs,
        )
    if name == "data_type_handler":
        return data_type_handler.create_app(store, jobs)
    if name == "histogram":
        return histogram.create_app(store, jobs)
    if name == "router":
        # The fleet router (serve/router.py): placement-aware predict
        # proxy + residency view, launched as its own LO_SERVICE —
        # never part of the all-in-one seven (start_all), because a
        # router in front of zero replicas routes nothing.
        from learningorchestra_tpu.serve import router as _router

        return _router.create_app(store)
    if name in ("tsne", "pca"):
        create = None
        if dispatcher is not None:
            def create(parent_filename, label_name, output_filename):
                dispatcher.submit(
                    "embedding_image",
                    {
                        "parent_filename": parent_filename,
                        "label_name": label_name,
                        "output_filename": output_filename,
                        "method": name,
                    },
                )
        return images.create_app(
            store, os.path.join(images_dir, name), name, create=create,
            jobs=jobs,
        )
    raise KeyError(f"unknown service {name!r}")


def build_apps(
    store: DocumentStore,
    images_dir: str,
    dispatcher=None,
    models_dir: str = "",
    jobs: "JobManager | None" = None,
) -> dict[int, object]:
    # One shared JobManager unless the caller brings their own: the
    # seven services must share a scheduler or the device class cannot
    # serialize builds against embeddings.
    jobs = jobs or make_job_manager(store)
    return {
        port: build_app(name, store, images_dir, dispatcher, models_dir, jobs)
        for name, port in SERVICES.items()
    }


# One fallback collector per (process, store): main() starts it before
# start_all, and start_all starts it for embedded callers (tests, the
# verify drive) — whoever gets there first wins, the other is a no-op.
_COLLECTORS: dict[int, object] = {}
_COLLECTORS_LOCK = threading.Lock()


def maybe_start_collector(
    store: DocumentStore, instance: str = "runner", service: str = "runner"
):
    """Start the single-process fallback TSDB collector for ``store``
    unless one is already running, collection is disabled
    (``LO_TSDB_COLLECT=0`` — the cluster driver owns the scrape), or the
    interval is zero. Returns the Collector, or None when gated off."""
    from learningorchestra_tpu.telemetry import metrics as _metrics
    from learningorchestra_tpu.telemetry import tsdb as _tsdb

    if not (_tsdb.collect_enabled() and _tsdb.metrics_interval_s() > 0):
        return None
    with _COLLECTORS_LOCK:
        collector = _COLLECTORS.get(id(store))
        if collector is None:
            collector = _tsdb.Collector(
                store,
                _metrics.global_registry(),
                instance=instance,
                service=service,
            ).start()
            _COLLECTORS[id(store)] = collector
    return collector


def start_all(
    store: Optional[DocumentStore] = None,
    images_dir: Optional[str] = None,
    host: str = "127.0.0.1",
    ephemeral: bool = False,
    dispatcher=None,
    models_dir: str = "",
    jobs: "JobManager | None" = None,
) -> tuple[DocumentStore, list[ServerThread]]:
    """Start all seven services on their reference ports; returns the
    shared store and the server threads (callers stop() them).

    ``ephemeral=True`` binds OS-assigned ports instead (tests can't
    assume 5000-5006 are free); each server's ``canonical_port`` records
    which reference port it stands in for, its ``port`` the actual bind.
    """
    store = store if store is not None else InMemoryStore()
    images_dir = images_dir or os.path.join(os.getcwd(), "lo_images")
    maybe_start_collector(store)
    servers = []
    apps = build_apps(store, images_dir, dispatcher, models_dir, jobs)
    for port, app in apps.items():
        server = ServerThread(app, host, 0 if ephemeral else port)
        server.canonical_port = port
        servers.append(server.start())
    return store, servers


def main() -> None:
    from learningorchestra_tpu.core.store_service import connect
    from learningorchestra_tpu.parallel.multihost import initialize_from_env

    # Join the multi-host device runtime first if the deployment asks for
    # one (LO_COORDINATOR/LO_NUM_PROCESSES/LO_PROCESS_ID): the compute
    # services then see the global mesh — the reference's "add spark
    # workers" knob (README.md:94) as an environment setting. One jax
    # process per host: run the all-in-one runner (or one compute
    # service) per host, not seven LO_SERVICE processes each trying to
    # join as the same process_id.
    service = _str_env("LO_SERVICE")  # lo: allow[LO301]
    print(
        "runner starting: "
        # boot banner; name-set knobs checked by runner/multihost at
        # boot, not range-checkable by the preflight
        f"LO_SERVICE={service!r} "
        f"LO_COORDINATOR={_str_env('LO_COORDINATOR')!r} "  # lo: allow[LO301]
        f"LO_PROCESS_ID={_str_env('LO_PROCESS_ID')!r} "  # lo: allow[LO301]
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}",
        flush=True,
    )
    multi_host = initialize_from_env()
    # ...and what JAX actually brought up (after the distributed join,
    # which must precede backend init): the env var above is a request,
    # this line is the answer
    if service is None or service in DEVICE_SERVICES:
        print(claim_devices(), flush=True)

    # Fail fast on a malformed device-cache budget — the same startup
    # posture as the scheduler knobs: a typo'd LO_DEVCACHE_BYTES must
    # not silently run at the default capacity.
    from learningorchestra_tpu.core.devcache import capacity_bytes

    print(f"devcache capacity: {capacity_bytes()} bytes", flush=True)

    # Same fail-fast posture for the serving knobs: a typo'd
    # LO_SERVE_BYTES must not silently serve at the default budget.
    from learningorchestra_tpu.serve import config as serve_config

    print(f"serving config: {serve_config.validate_all()}", flush=True)

    # ...and the fleet knobs (docs/serving.md "Fleet"): an operator
    # should see at boot whether this process is a fleet replica (and
    # which index) or a plain single serving plane, and a typo'd
    # LO_FLEET_RF must refuse bring-up, never silently place models
    # with the wrong replication
    from learningorchestra_tpu.serve import fleet as serve_fleet

    print(f"fleet config: {serve_fleet.validate_env()}", flush=True)

    # ...and the coalescing knobs (docs/scheduler.md): window 0 means
    # passthrough, which an operator should see stated at boot
    from learningorchestra_tpu.sched import config as sched_config

    print(
        "coalescing config: "
        f"window_s={sched_config.coalesce_window_s()} "
        f"max_jobs={sched_config.coalesce_max_jobs()}",
        flush=True,
    )

    # ...and the crash-resume knobs (docs/robustness.md): an operator
    # should see at boot whether orphaned builds will resume or fail,
    # and a typo'd LO_RESUME must refuse bring-up, never silently pick
    # a side
    print(
        "resume config: "
        f"enabled={sched_config.resume_enabled()} "
        f"every_segments={sched_config.resume_every_segments()}",
        flush=True,
    )

    # ...and the zero-copy wire knobs (docs/dataplane.md): shm_bytes 0
    # means frames ride the HTTP body — an operator expecting the ring
    # should see that stated at boot, and a typo'd LO_DTYPE_POLICY
    # must refuse bring-up, never silently fit at the wrong precision
    from learningorchestra_tpu.core import shmring
    from learningorchestra_tpu.utils.dtypepolicy import dtype_policy

    print(
        f"wire config: shm_bytes={shmring.shm_bytes()} "
        f"dtype_policy={dtype_policy()} "
        f"v2={_flag_env('LO_WIRE_V2', default=True)}",
        flush=True,
    )

    # ...and the sharding knobs (docs/dataplane.md): an operator should
    # see at boot how many shard groups this process routes across (the
    # ';' groups of LO_STORE_URL — 1 means the unsharded wire path) and
    # which stripe geometry a first write would seed; a typo'd
    # LO_SHARD_STRIPE_ROWS must refuse bring-up, never silently seed an
    # unintended placement into the fleet's shard map
    from learningorchestra_tpu.core import shardmap

    # lo: allow[LO301] free-form URL; unset = in-process store
    store_url = _str_env("LO_STORE_URL", "")
    shard_groups = len([g for g in store_url.split(";") if g.strip()]) or 1
    print(
        f"shard config: groups={shard_groups} "
        f"stripe_rows={shardmap.stripe_rows()} "
        f"map_ttl_s={shardmap.map_ttl_s()}",
        flush=True,
    )

    # ...and the web-serving knobs (docs/web.md): LO_WEB_ASYNC=0 is the
    # threaded escape hatch — an operator should see at boot which
    # serving core is live, and a typo'd LO_WEB_HANDLERS must refuse
    # bring-up, never silently serve at the default width
    from learningorchestra_tpu.utils import webloop

    print(f"web config: {webloop.validate_env()}", flush=True)

    # ...and the AOT compile-plane knobs (docs/compile.md): whether the
    # boot precompile pass runs, how much of the manifest it covers,
    # and whether executables publish to the fleet — a typo'd LO_AOT
    # must refuse bring-up, never silently boot cold
    from learningorchestra_tpu.compile import config as compile_config

    print(f"compile config: {compile_config.validate_env()}", flush=True)

    data_dir = _str_env("LO_DATA_DIR", os.path.join(os.getcwd(), "lo_data"))
    from learningorchestra_tpu.utils.jitcache import enable_compile_cache

    print(f"compile cache: dir={enable_compile_cache()}", flush=True)
    if service is None or service == "database_api":
        # resolve (and if need be build) the ingest parser now, so a
        # missing toolchain is a boot line, not a slow first ingest
        from learningorchestra_tpu.native.loader import parser_status

        print(f"csv parser: {parser_status()}", flush=True)
    # lo: allow[LO301] free-form volume path, no domain to preflight
    images_dir = _str_env(
        "LO_IMAGES_DIR", os.path.join(data_dir, "images")
    )
    models_dir = _str_env(
        "LO_MODELS_DIR", os.path.join(data_dir, "models")
    )
    host = _str_env("LO_HOST", "127.0.0.1")

    if store_url:
        store = connect(store_url)
    else:
        store = InMemoryStore(data_dir=data_dir)

    dispatcher = None
    if multi_host:
        import jax

        if not store_url:
            # Every process of the mesh must see the SAME datasets; a
            # per-process InMemoryStore would leave workers reading an
            # empty store and the coordinator waiting forever in its
            # first cross-host collective. Refuse to start.
            raise SystemExit(
                "multi-host mode requires LO_STORE_URL: all processes "
                "must share one store server "
                "(python -m learningorchestra_tpu.core.store_service)"
            )
        if _str_env("LO_MODELS_DIR") is None:
            # Same reasoning for checkpoints: predict-from-checkpoint
            # broadcasts the artifact path to every process, so the
            # models dir must be a volume all hosts mount — not each
            # host's local disk. Make the choice explicit.
            raise SystemExit(
                "multi-host mode requires LO_MODELS_DIR pointing at a "
                "volume shared by all hosts (set it to '' to disable "
                "checkpointing)"
            )
        print(
            f"multi-host runtime: process {jax.process_index()}/"
            f"{jax.process_count()}, {jax.device_count()} global devices",
            flush=True,
        )
        dispatcher = make_dispatcher(store, images_dir)
        # keep idle workers' pending broadcast inside the transport's
        # collective deadline (see SpmdDispatcher.start_heartbeat)
        dispatcher.start_heartbeat()
        if jax.process_index() > 0:
            # Worker host: no REST surface — execute the jobs the
            # coordinator broadcasts (the spark-worker role,
            # reference docker-compose.yml:123-163).
            print("spmd worker: waiting for jobs", flush=True)
            dispatcher.run_worker_loop()
            return

    # One scheduler + journal for every service this process runs.
    # Scope the journal to the service in the one-process-per-service
    # topology so each restarted process recovers only its own jobs
    # from the shared store. Recovery runs BEFORE the REST surface
    # accepts traffic: never-started jobs re-enqueue, orphaned RUNNING
    # jobs go FAILED with finished:true so pollers terminate — the
    # crash the reference hangs on (docs/scheduler.md).
    # ...and the fleet-observability knobs (docs/observability.md): a
    # typo'd LO_SLO_* threshold must refuse bring-up, and an operator
    # should see at boot whether this process self-scrapes into the
    # store-backed TSDB ring or defers to a cluster driver
    # (deploy/cluster.py sets LO_TSDB_COLLECT=0 and collects centrally
    # through POST /metrics/ingest).
    from learningorchestra_tpu.telemetry import slo as _slo
    from learningorchestra_tpu.telemetry import tracing as _tracing
    from learningorchestra_tpu.telemetry import tsdb as _tsdb

    print(
        "observability config: "
        f"collect={_tsdb.collect_enabled()} "
        f"interval_s={_tsdb.metrics_interval_s()} "
        f"points={_tsdb.tsdb_points()} "
        f"trace_ring={_tracing.trace_ring()} "
        f"slo={_slo.validate_env()}",
        flush=True,
    )
    maybe_start_collector(
        store, instance=service or "runner", service=service or "runner"
    )

    # The AOT compile plane (docs/compile.md): fleet-fetch serialized
    # executables into the local jit cache, precompile the manifest in
    # the background (a daemon thread — compilation is host CPU work,
    # it never occupies a device-class scheduler slot), publish fresh
    # entries back. Gated on LO_AOT; the kill -9 restart drill rides
    # this — a restarted runner pulls its own previously published
    # programs and replays with zero compile misses.
    from learningorchestra_tpu.compile import boot_compile_plane

    if boot_compile_plane(store=store, models_dir=models_dir or ""):
        print("aot compile plane: precompiling in background", flush=True)

    jobs = make_job_manager(store, scope=service or "all")
    recovered = recover_jobs(store, jobs)
    if recovered["requeued"] or recovered["orphaned"]:
        print(
            "job recovery: "
            f"{len(recovered['requeued'])} re-enqueued, "
            f"{len(recovered['orphaned'])} orphaned jobs marked failed",
            flush=True,
        )

    if service:
        port = _int_env(
            "LO_PORT",
            ROUTER_PORT if service == "router" else SERVICES[service],
        )
        server = ServerThread(
            build_app(service, store, images_dir, dispatcher, models_dir, jobs),
            host,
            port,
        )
        server.start()
        print(f"service {service} on {host}:{server.port}", flush=True)
        servers = [server]
        if (
            service == "model_builder"
            and serve_fleet.replica_index() is not None
        ):
            # This process is a fleet replica: run the agent that pins
            # this replica's placement-assigned checkpoints (warming
            # them at the serve shape) and heartbeats residency into
            # the store the router reads. Uses the process-wide plane —
            # the same one create_app serves predicts from.
            from learningorchestra_tpu.serve import global_serve_plane

            agent = serve_fleet.ReplicaAgent(
                store,
                models_dir or "",
                global_serve_plane(),
                url=f"http://{host}:{server.port}",
            ).start()
            print(
                f"fleet replica {agent.index}: agent started "
                f"(interval {agent.interval_s}s)",
                flush=True,
            )
    else:
        _, servers = start_all(
            store,
            images_dir,
            host,
            ephemeral=_flag_env("LO_EPHEMERAL"),
            dispatcher=dispatcher,
            models_dir=models_dir,
            jobs=jobs,
        )
        port_names = {port: name for name, port in SERVICES.items()}
        for server in servers:
            name = port_names[server.canonical_port]
            print(f"service {name} on {host}:{server.port}", flush=True)
        print(
            f"learningorchestra_tpu serving all services (host {host}); "
            f"data in {data_dir}",
            flush=True,
        )
    try:
        for server in servers:
            server._thread.join()
    except KeyboardInterrupt:
        for server in servers:
            server.stop()


if __name__ == "__main__":
    main()
