"""Model builder: the flagship pipeline — preprocess, fit N classifiers
concurrently, evaluate, persist predictions.

Reference behaviour (microservices/model_builder_image/model_builder.py:
133-247): load train+test dataframes, ``exec`` user preprocessing, fan
out one thread per requested classifier onto the shared Spark cluster
(FAIR scheduler), time the fit, evaluate weighted-F1/accuracy when an
evaluation split exists, then ``collect()`` predictions to the driver and
insert them row-by-row.

TPU-native differences: classifiers fit as jitted programs on the shared
device mesh (threads overlap host prep and keep the reference's
task-parallel shape, reference model_builder.py:94,159-175); predictions
are written back in batched columnar writes, not 1 RPC per row.
"""

from __future__ import annotations

import itertools
import os
import threading
import traceback
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Callable, Optional

import numpy as np
from jax.sharding import Mesh

from learningorchestra_tpu.core.columns import Column
from learningorchestra_tpu.core.store import DocumentStore, ROW_ID
from learningorchestra_tpu.core.table import insert_columns_batched
from learningorchestra_tpu.frame.dataframe import DataFrame
from learningorchestra_tpu.frame.pyspark_compat import run_preprocessor
from learningorchestra_tpu.ml import progress as _progress
from learningorchestra_tpu.ml.base import CLASSIFIER_NAMES, make_classifier
from learningorchestra_tpu.sched import cancel as _cancel
from learningorchestra_tpu.sched import config as _sched_config
from learningorchestra_tpu.sched.cancel import JobCancelledError, check_cancelled
from learningorchestra_tpu.telemetry import tracing as _tracing
from learningorchestra_tpu.testing import faults as _faults
from learningorchestra_tpu.utils.dtypepolicy import dtype_policy
from learningorchestra_tpu.utils.profiling import PhaseTimer, trace

FEATURES_COL = "features"
LABEL_COL = "label"

# Guards the process-global JAX profiler (see build_model's trace note).
_TRACE_LOCK = threading.Lock()

# Serializes collective device dispatches on a single-process CPU
# backend (the KNOWN LATENT from PR 8, now guarded): with
# --xla_force_host_platform_device_count=N the "devices" are threads of
# one host pool, and XLA's CPU collective rendezvous can deadlock when
# two already-compiled collective programs execute concurrently — each
# program's participants grab part of the pool and wait for peers that
# the other program's participants are occupying. Real accelerator
# backends serialize dispatches through the device queue, and the
# scheduler's width-1 device class protects the product path; this lock
# protects direct library/test callers running concurrent builds. It is
# a no-op (never taken) off CPU or under multi-process SPMD.
_CPU_RENDEZVOUS_LOCK = threading.Lock()


def _collective_dispatch_guard():
    """The context manager for one collective dispatch+fetch: the CPU
    rendezvous lock when the backend is single-process CPU with
    virtual devices, else a free pass."""
    import contextlib

    import jax

    if (
        jax.process_count() == 1
        and jax.default_backend() == "cpu"
        and jax.local_device_count() > 1
    ):
        return _CPU_RENDEZVOUS_LOCK
    return contextlib.nullcontext()

# Capture directories are named from the JOB (dataset name + build
# sequence number), never the wall clock: this line once used
# ``int(time.time() * 1000)``, which on a multi-host mesh computes a
# DIFFERENT name on every process — the bug class that motivated the
# analyzer's LO102 broadcast-determinism rule (analysis/rules.py; the
# rule itself checks broadcast/dispatch payloads, not artifact paths).
# Tracing is also coordinator-only now, but the deterministic name
# keeps captures correlatable with their request across hosts and runs.
_TRACE_SEQ = itertools.count()


def _next_trace_dir(trace_root: str, test_filename: str) -> str:
    for seq in _TRACE_SEQ:
        path = os.path.join(trace_root, f"build_{test_filename}_{seq:03d}")
        try:
            # makedirs IS the reservation: an exists() probe would let
            # two server processes sharing LO_TRACE_DIR claim the same
            # name before either profiler writes it
            os.makedirs(path)
        except FileExistsError:  # taken by an earlier run or a peer
            continue
        return path
    raise AssertionError("unreachable: itertools.count is infinite")


def load_dataframe(store: DocumentStore, filename: str) -> DataFrame:
    """Dataset → DataFrame, metadata row/fields excluded (the reference
    drops the metadata document and its fields, model_builder.py:96-116).

    Reads through the device cache's host tier (core/devcache.py): the
    second build/predict over the same collection revision skips the
    wire read and frame decode — the reference re-reads Mongo per
    request instead (model_builder.py:96-116)."""
    from learningorchestra_tpu.core.devcache import dataset_table

    return DataFrame.from_table(dataset_table(store, filename))


class PredictionWriter:
    """Overlapped prediction write-back: one background thread drains
    per-classifier store writes while the NEXT classifier fits — the
    write tail leaves the build's critical path (the reference's
    untimed collect()+insert tail, model_builder.py:232-247, was ours
    too, just batched).

    One writer thread, not a pool: per-collection write order is
    preserved (rows before the metadata document — the contract
    write_documents states), and the shared store sees at most one bulk
    writer per build. ``barrier()`` is the end-of-job fence build_model
    runs before returning: every submitted write has finished (or its
    exception re-raises and fails the job), so the 201/finished
    contract and the persisted per-phase timings stay honest — the
    "write" phase is measured on the writer thread around the actual
    store calls."""

    def __init__(self):
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="lo-writeback"
        )
        self._futures: list = []
        self._lock = threading.Lock()

    def submit(self, fn, name: Optional[str] = None) -> None:
        context = _tracing.capture()

        def run():
            with _tracing.attach(context):
                return fn()

        with self._lock:
            self._futures.append((name, self._pool.submit(run)))

    def barrier(self) -> list:
        """Drain every pending write; returns ``[(name, exception)]``
        for the writes that failed instead of raising — a failed
        write-back fails THAT classifier's outcome (the partial-results
        contract), not the whole build."""
        self._pool.shutdown(wait=True)
        with self._lock:
            futures, self._futures = self._futures, []
        failures = []
        for name, future in futures:
            error = future.exception()
            if error is not None:
                failures.append((name, error))
        return failures


def _prediction_columns(predicted_df: DataFrame) -> dict[str, Column]:
    """Column-major view of a prediction frame as typed columns: every
    column except the assembled ``features`` vector (the reference also
    deletes ``rawPrediction``, which we never materialize),
    ``probability`` as a fixed-width ``vec`` column — the (rows, classes)
    matrix goes to the store as one float64 buffer and materializes as
    per-row plain lists only at document reads (reference
    model_builder.py:232-247 boxes it per row at driver collect time).
    Numeric columns hand their buffers to the store directly — no
    per-value float()/isnan loops (the tail the reference never fixed,
    model_builder.py:237-247)."""
    out: dict[str, Column] = {}
    for name in predicted_df.columns:
        if name == FEATURES_COL:
            continue
        column = predicted_df._column(name)
        if column.ndim > 1:
            out[name] = Column.from_numpy(
                np.asarray(column, dtype=np.float64)
            )
        elif column.dtype == object:
            out[name] = Column.from_values(column.tolist())
        else:
            out[name] = Column.from_numpy(
                np.asarray(column, dtype=np.float64)
            )
    return out


def train_one(
    store: DocumentStore,
    classificator_name: str,
    features_training: DataFrame,
    features_testing: DataFrame,
    features_evaluation: Optional[DataFrame],
    prediction_filename: str,
    mesh: Optional[Mesh] = None,
    write_outputs: bool = True,
    models_dir: Optional[str] = None,
    writer: Optional[PredictionWriter] = None,
    sink: Optional[_progress.ProgressSink] = None,
    on_durable: Optional[Callable[[dict], None]] = None,
) -> dict:
    """Fit + evaluate + persist one classifier (the reference's
    ``classificator_handler``, model_builder.py:178-230). Returns the
    prediction collection's metadata document — complete only after the
    build's write barrier when a ``writer`` is given (build_model hands
    each classifier the shared background writer so this classifier's
    store writes overlap the next one's fit; None = write synchronously,
    the contract for direct callers).

    ``write_outputs=False`` runs the full compute path (fit, evaluate,
    predict — all of which enter cross-host collectives and must run on
    every process of a multi-host mesh) but skips the store writes: SPMD
    worker processes pass False so the shared store sees exactly one
    writer (parallel/spmd.py).

    ``models_dir`` (or ``LO_MODELS_DIR``) persists the fitted model as a
    checkpoint named after the prediction collection, recorded in the
    metadata as ``model_checkpoint`` — the durability the reference
    lacks (its models die with the request, model_builder.py:232-247;
    SURVEY.md §5 flags this); :func:`predict_with_model` serves
    predictions from the artifact without refitting.

    ``sink`` makes the fit crash-resumable: it is bound as the ambient
    progress sink around the fit, so the segment loops persist progress
    artifacts (ml/progress.py). ``on_durable(metadata)`` fires once this
    classifier's outputs have durably landed (after the metadata insert
    — on the writer thread when writes overlap); build_model journals
    the per-classifier completion there."""
    output_name = f"{prediction_filename}_prediction_{classificator_name}"
    metadata = {
        "filename": output_name,
        "classificator": classificator_name,
        ROW_ID: 0,
    }
    timer = PhaseTimer()

    # Cooperative cancellation (DELETE /jobs/<name>): phase boundaries
    # are the abort points — no-op outside a scheduled job and on SPMD
    # worker processes (they carry no token; a coordinator-side abort
    # mid-collective-stream poisons the dispatcher like any mid-job
    # failure, and the supervisor restarts the runtime).
    check_cancelled()
    X_train = features_training.feature_matrix(FEATURES_COL)
    y_train = features_training.label_vector(LABEL_COL)

    classifier = make_classifier(classificator_name, mesh=mesh)
    _faults.fire(
        "builder.phase", phase="fit", classificator=classificator_name
    )
    # dtype rides the phase attrs so a trace says which LO_DTYPE_POLICY
    # (f32 vs bf16 feature matrices) produced these numbers
    with timer.phase("fit", rows=len(X_train), dtype=dtype_policy()):
        # the rendezvous guard serializes the whole dispatch+drain on a
        # single-process CPU backend (see _CPU_RENDEZVOUS_LOCK); a
        # no-op on real accelerators and under multi-process SPMD
        with _collective_dispatch_guard(), _progress.bind_sink(sink):
            model = classifier.fit(X_train, y_train)
            # drain the async dispatch queue inside the fit phase:
            # without this the device time lands on whichever later
            # call blocks first, and "evaluate"/"predict" report the
            # fit's tail (VERDICT r4 weak #5 — the phase numbers must
            # mean something). Its own span, so the trace tells the
            # fit's host work from its wait for the device
            _tracing.device_wait("fit:device_wait", model.device_state())
    metadata["fit_time"] = timer.timings["fit"]
    check_cancelled()  # phase boundary: fit done, before checkpoint/eval

    # None = "no caller preference" → env fallback; "" = explicitly
    # disabled. The distinction matters on a multi-host mesh: the SPMD
    # payload carries one resolved value to every process, so whether
    # the (collective) checkpoint gather runs is decided identically
    # everywhere — a per-host env fallback on "" would desynchronize.
    if models_dir is None:
        # free-form volume path: no numeric domain to preflight, and
        # lo: allow[LO305] — read here so every process resolves one
        models_dir = os.environ.get("LO_MODELS_DIR")  # lo: allow[LO301]
    if models_dir:
        from learningorchestra_tpu.ml.checkpoint import (
            checkpoint_path,
            gather_model,
            write_checkpoint,
        )

        artifact = checkpoint_path(models_dir, output_name)
        _faults.fire(
            "builder.phase",
            phase="checkpoint",
            classificator=classificator_name,
        )
        with timer.phase("checkpoint"):
            # the gather may be a cross-host collective (model-axis
            # sharded params): ALL processes enter it; only the
            # coordinator touches the filesystem
            with _collective_dispatch_guard():
                gathered = gather_model(model)
            if write_outputs:
                os.makedirs(models_dir, exist_ok=True)
                write_checkpoint(gathered, artifact)
        if write_outputs:
            metadata["model_checkpoint"] = artifact
            # publish-time serve warmup (compile plane): hand the serve
            # path the chance to precompile this artifact's fixed
            # dispatch shape before the first POST /predict asks for
            # it. Feature width rides along — tree checkpoints don't
            # record it. No-op unless a service registered a handler;
            # never raises into the build.
            from learningorchestra_tpu import compile as lo_compile

            lo_compile.checkpoint_published(
                artifact, features=int(X_train.shape[1])
            )

    prediction = None
    if features_evaluation is not None:
        # Sharded once, shared across all classifier threads (cached on
        # the frame) — N models, one host→device transfer. build_model
        # aliases features_evaluation to features_testing when their
        # content matches (the documented product path), so X_eval IS
        # X_test below and evaluate+predict share one forward pass and
        # one device→host transfer.
        X_eval = features_evaluation.device_matrix(FEATURES_COL, model.mesh)
        y_eval = features_evaluation.device_labels(LABEL_COL, model.mesh)
        X_test = features_testing.device_matrix(FEATURES_COL, model.mesh)
        _faults.fire(
            "builder.phase",
            phase="evaluate",
            classificator=classificator_name,
        )
        with timer.phase("evaluate", rows=features_evaluation.count()):
            # the collective eval is THE dispatch the PR 8 latent
            # deadlock fired on: two warm builds' evals interleaving
            # on the virtual-device CPU pool (regression-tested by
            # test_builder.test_two_warm_builds_complete_concurrently)
            with _collective_dispatch_guard():
                accuracy, weighted_f1, labels, probs = (
                    model.evaluate_predict(X_eval, y_eval, X_test)
                )
            prediction = (labels, probs)
            # Stored as strings, matching the reference's metadata document
            # (model_builder.py:223-224, values shown in docs/database_api.md).
            metadata["F1"] = str(weighted_f1)
            metadata["accuracy"] = str(accuracy)

    return _predict_and_write(
        store,
        model,
        features_testing,
        output_name,
        metadata,
        timer,
        write_outputs,
        prediction=prediction,
        writer=writer,
        on_durable=on_durable,
    )


def _predict_and_write(
    store: DocumentStore,
    model,
    features_testing: DataFrame,
    output_name: str,
    metadata: dict,
    timer: PhaseTimer,
    write_outputs: bool,
    prediction: Optional[tuple] = None,
    writer: Optional[PredictionWriter] = None,
    on_durable: Optional[Callable[[dict], None]] = None,
) -> dict:
    """Predict over the test frame and persist the prediction
    collection + its metadata document — the shared tail of
    :func:`train_one` and :func:`predict_with_model`.

    Written directly (not via write_documents): prediction metadata has
    no ``finished`` flag in the reference either (model_builder.py:
    191-196; document shape shown in docs/database_api.md:76-83). The
    bulk prediction write is timed as its own phase — it is the
    reference's wall-clock tail (driver collect() + row-wise inserts,
    model_builder.py:232-247) and the number the benchmark reports.

    With a ``writer``, the store writes run on the build's background
    writer thread overlapped with the next classifier's fit; the host
    column prep stays on THIS thread (it reads the predicted frame),
    the ``write`` phase is timed around the actual store calls on the
    writer thread, and the metadata document — including the timings —
    still lands strictly after the rows. build_model's barrier
    guarantees the returned metadata is complete before the job
    reports finished.
    """
    if prediction is None:  # no eval split: predict is its own pass
        X_test = features_testing.device_matrix(FEATURES_COL, model.mesh)
        _faults.fire(
            "builder.phase",
            phase="predict",
            classificator=metadata.get("classificator"),
        )
        with timer.phase("predict", rows=features_testing.count()):
            # one forward pass yields labels AND probabilities
            with _collective_dispatch_guard():
                prediction = model.predict_both(X_test)
    labels, probability = prediction
    predicted_df = features_testing.withColumn(
        "prediction", labels.astype(np.float64)
    ).withColumn("probability", probability)

    if not write_outputs:
        metadata["timings"] = timer.as_metadata()
        return metadata

    write_rows = predicted_df.count()
    # the prediction frame carries every input field: on a wide table
    # the typed-column copies and the write follow ``fields``, not rows
    with _tracing.span("write:columns", rows=write_rows):
        columns = _prediction_columns(predicted_df)
        _tracing.annotate(fields=len(columns))
    write_bytes = sum(
        int(column.resident_nbytes()) for column in columns.values()
    )

    def flush() -> None:
        _faults.fire(
            "builder.phase",
            phase="write",
            classificator=metadata.get("classificator"),
        )
        with _tracing.span("write:drop"):
            store.drop(output_name)
        with timer.phase(
            "write", rows=write_rows, bytes=write_bytes, fields=len(columns)
        ):
            insert_columns_batched(store, output_name, columns)
        metadata["timings"] = timer.as_metadata()
        with _tracing.span("write:metadata"):
            store.insert_one(output_name, metadata)
        # the metadata document is the durability proof (it lands
        # strictly after the rows): only now is this classifier's
        # completion journal-worthy
        if on_durable is not None:
            on_durable(metadata)

    if writer is None:
        flush()
    else:
        writer.submit(flush, metadata.get("classificator"))
    return metadata


def _alias_if_equal(
    features_evaluation: Optional[DataFrame], features_testing: DataFrame
) -> Optional[DataFrame]:
    """The documented preprocessor evaluates on the test frame
    (reference docs/model_builder.md: ``features_evaluation =
    assembler.transform(testing_df)``). The assembler remembers a
    frame's assembly, so that second transform returns the test frame
    itself and the identity test answers. A preprocessor that reaches
    an equal evaluation frame another way gets the content compare:
    aliasing the two lets the per-frame device cache share one
    host→device transfer and evaluate_predict share one forward pass."""
    if features_evaluation is None or features_evaluation is features_testing:
        return features_evaluation
    try:
        eval_X = features_evaluation.feature_matrix(FEATURES_COL)
        test_X = features_testing.feature_matrix(FEATURES_COL)
        eval_y = features_evaluation.label_vector(LABEL_COL)
        test_y = features_testing.label_vector(LABEL_COL)
    except (KeyError, TypeError, ValueError):
        return features_evaluation
    if (
        eval_X.shape == test_X.shape
        and np.array_equal(eval_X, test_X)
        and np.array_equal(eval_y, test_y)
    ):
        return features_testing
    return features_evaluation


class _ResumedMemberFailure(RuntimeError):
    """A classifier the pre-crash run already journaled as permanently
    failed: the resumed build records the original error without
    re-running the member."""


def _fold_resume(resume: Optional[list]) -> dict[str, dict]:
    """Journaled ``progress`` events → per-classifier terminal status.
    Later events win (a ``failed`` member re-journaled ``finished`` by
    a later resume attempt is finished). Segment events carry no
    ``status`` and fold to nothing — the fits read their own progress
    artifacts, which hold strictly more than the journal line."""
    done: dict[str, dict] = {}
    for event in resume or []:
        name = event.get("classificator")
        status = event.get("status")
        if name and status in ("finished", "failed"):
            done[name] = {"status": status, "error": event.get("error")}
    return done


def _mesh_key(mesh: Optional[Mesh]) -> str:
    """The (resolved) mesh's structural signature as a string — the
    progress artifact's mesh-layout validation component."""
    from learningorchestra_tpu.core.devcache import mesh_signature
    from learningorchestra_tpu.ml.base import resolve_mesh

    return str(mesh_signature(resolve_mesh(mesh)))


def build_model(
    store: DocumentStore,
    training_filename: str,
    test_filename: str,
    preprocessor_code: str,
    classificators_list: list[str],
    mesh: Optional[Mesh] = None,
    write_outputs: bool = True,
    models_dir: Optional[str] = None,
    resume: Optional[list] = None,
) -> list[dict]:
    """The reference's ``build_model`` (model_builder.py:133-176):
    preprocess once, then one thread per classifier.

    ``resume`` is the journaled ``progress`` event list recovery hands
    a re-enqueued build (sched/recovery.py): classifiers it records as
    durably finished are skipped (their stored metadata is returned),
    ones it records as permanently failed stay failed without a re-run,
    and everything else refits — each fit picking up its own progress
    artifact, so only the remaining segments execute."""
    import jax

    unknown = [n for n in classificators_list if n not in CLASSIFIER_NAMES]
    if unknown:
        raise KeyError(f"invalid classificator names {unknown}")

    # Captured ONCE on the job worker thread (contextvars do not cross
    # the per-classifier pool below): the handle is how the build
    # journals per-classifier completions and attaches the partial-
    # results detail to its own record. None for library callers.
    from learningorchestra_tpu.core.jobs import current_job_handle

    handle = current_job_handle()

    # Span-per-stage: with phase spans from each train_one's PhaseTimer
    # these cover the build end to end, so /jobs/<name>/trace accounts
    # for (nearly) the whole job wall-clock — the 61%-dtype-cast class
    # of fact becomes a one-request diagnosis.
    _faults.fire("builder.phase", phase="load_data")
    with _tracing.span("load_data"):
        training_df = load_dataframe(store, training_filename)
        testing_df = load_dataframe(store, test_filename)
        _tracing.annotate(rows=training_df.count() + testing_df.count())
    _faults.fire("builder.phase", phase="preprocess")
    with _tracing.span("preprocess"):
        out = run_preprocessor(preprocessor_code, training_df, testing_df)
        _tracing.annotate(rows=out["features_training"].count())
        out["features_evaluation"] = _alias_if_equal(
            out["features_evaluation"], out["features_testing"]
        )

    # Multi-host SPMD: every process must dispatch the classifiers'
    # device programs in the SAME order, and thread scheduling is not
    # deterministic across hosts — serialize the fan-out. Single-host
    # keeps the reference's thread-per-classifier shape
    # (model_builder.py:159-175). LO_BUILD_WORKERS caps the fan-out:
    # N concurrent fits hold N models' device working sets at once, and
    # past ~1M rows per classifier that can exceed one chip's HBM (the
    # fits are device-queue-serialized anyway, so capping costs little
    # wall-clock; the 10M-row scale proof runs with LO_BUILD_WORKERS=1).
    multi_process = jax.process_count() > 1
    if multi_process:
        max_workers = 1
    else:
        max_workers = len(classificators_list) or 1
        # lo: allow[LO305] validated in place with its own error below
        cap = os.environ.get("LO_BUILD_WORKERS", "").strip()
        if cap:
            try:
                max_workers = max(1, min(max_workers, int(cap)))
            except ValueError:
                raise ValueError(
                    f"LO_BUILD_WORKERS must be an integer, got {cap!r}"
                ) from None
    # LO_TRACE_DIR: device-level tracing of the whole fan-out (fits,
    # predictions, writes) into a TensorBoard/Perfetto profile dir —
    # one capture per build, named after the test dataset. The JAX
    # profiler is process-global and non-reentrant, so a build that
    # overlaps an active capture runs untraced rather than failing:
    # tracing is observability, never a reason to 500 a request.
    # Coordinator-only (write_outputs), like every other host-side
    # artifact (parallel/spmd.py:19-21): worker processes run the same
    # compute but must not write to the trace volume.
    # lo: allow[LO301,LO305] free-form profile-dir path, per-build read
    trace_root = os.environ.get("LO_TRACE_DIR")
    trace_dir = None
    tracing = (
        trace_root and write_outputs and _TRACE_LOCK.acquire(blocking=False)
    )
    if tracing:
        try:
            trace_dir = _next_trace_dir(trace_root, test_filename)
        except OSError:  # unwritable/full trace volume: run untraced
            _TRACE_LOCK.release()
            tracing = False
    # Crash resume needs a durable home for progress artifacts: they
    # live beside the model checkpoints. Resolve the env fallback here
    # so the sink and train_one agree on one directory (train_one keeps
    # its own fallback for direct callers). Coordinator-only, single-
    # host only: a resumed in-process build cannot rejoin a multi-host
    # collective stream, so workers never persist progress.
    if models_dir is None:
        # lo: allow[LO305] same env fallback the sink and train_one use
        models_dir = os.environ.get("LO_MODELS_DIR")
    make_sink: Optional[Callable] = None
    if (
        write_outputs
        and models_dir
        and not multi_process
        and _sched_config.resume_enabled()
    ):
        # The devcache-style validation key: a progress artifact is
        # only resumable against the SAME input content, dtype policy,
        # and mesh layout that produced it — anything else is a clean
        # restart, never a silently-wrong model. Content fingerprints,
        # not collection revs: revs reseed per boot, and the restarted
        # process is the one that needs the artifact to validate.
        with _tracing.span(
            "resume:fingerprint",
            rows=training_df.count() + testing_df.count(),
        ):
            training_fp = _progress.collection_fingerprint(
                store, training_filename
            )
            test_fp = _progress.collection_fingerprint(store, test_filename)
        sink_meta = {
            "training_fp": training_fp,
            "test_fp": test_fp,
            "dtype_policy": dtype_policy(),
            "mesh": _mesh_key(mesh),
        }
        every = _sched_config.resume_every_segments()
        os.makedirs(models_dir, exist_ok=True)

        def make_sink(name: str) -> _progress.ProgressSink:
            output_name = f"{test_filename}_prediction_{name}"
            on_segment = None
            if handle is not None:
                def on_segment(seg: int, _name=name) -> None:
                    handle.progress(
                        classificator=_name, kind="segment", segment=seg
                    )
            return _progress.ProgressSink(
                _progress.progress_path(models_dir, output_name),
                dict(sink_meta),
                every=every,
                on_segment=on_segment,
            )

    try:
        return _build_model_traced(
            store,
            out,
            classificators_list,
            test_filename,
            mesh,
            write_outputs,
            models_dir,
            max_workers,
            trace_dir,
            resume_done=_fold_resume(resume),
            make_sink=make_sink,
            handle=handle,
        )
    finally:
        if tracing:
            _TRACE_LOCK.release()


def _build_model_traced(
    store,
    out,
    classificators_list,
    test_filename,
    mesh,
    write_outputs,
    models_dir,
    max_workers,
    trace_dir,
    resume_done=None,
    make_sink=None,
    handle=None,
) -> list[dict]:
    # contextvars don't cross pool threads: hand each worker the ambient
    # (trace, span) so its train span — and the PhaseTimer phases inside
    # — nest under the request/job trace, and the ambient cancel token
    # so DELETE /jobs/<name> reaches the per-classifier threads.
    context = _tracing.capture()
    cancel_token = _cancel.current_token()
    # Overlapped write-back (LO_WRITE_OVERLAP=0 restores synchronous
    # writes): coordinator-only host work — the writer thread touches
    # the store, never the device, so it cannot reorder SPMD dispatch.
    overlap = (
        # lo: allow[LO305] — per-build read: a mid-flight flip only
        # affects the NEXT build, never a writer already draining
        write_outputs
        and os.environ.get("LO_WRITE_OVERLAP", "1") != "0"  # lo: allow[LO305]
    )
    writer = PredictionWriter() if overlap else None
    resume_done = resume_done or {}

    def run_train(name: str) -> dict:
        with _tracing.attach(context), _cancel.bind(cancel_token):
            # a cancelled build stops launching classifiers: fits
            # already in flight run to their own next check inside
            # train_one, queued ones never start
            check_cancelled()
            sink = make_sink(name) if make_sink is not None else None
            prior = resume_done.get(name)
            if prior is not None and prior.get("status") == "failed":
                # journaled as permanently failed before the crash:
                # resume skips the member, keeping the original error
                if sink is not None:
                    sink.discard()
                raise _ResumedMemberFailure(
                    prior.get("error") or "failed before service restart"
                )
            if prior is not None and prior.get("status") == "finished":
                stored = store.find_one(
                    f"{test_filename}_prediction_{name}", {ROW_ID: 0}
                )
                if stored is not None:
                    # durably completed before the crash (the journal
                    # line lands only after the metadata insert): skip
                    # the refit, return the stored outcome
                    if sink is not None:
                        sink.discard()
                    return stored
                # journaled finished but the outputs are gone (dropped
                # collection): fall through and rebuild

            def durable(metadata, _name=name, _sink=sink) -> None:
                if handle is not None:
                    handle.progress(classificator=_name, status="finished")
                if _sink is not None:
                    _sink.discard()

            with _tracing.span(f"train:{name}", classificator=name):
                return train_one(
                    store,
                    name,
                    out["features_training"],
                    out["features_testing"],
                    out["features_evaluation"],
                    test_filename,
                    mesh,
                    write_outputs,
                    models_dir,
                    writer=writer,
                    sink=sink,
                    on_durable=durable,
                )

    try:
        with trace(trace_dir), ThreadPoolExecutor(
            max_workers=max_workers
        ) as pool:
            futures = [
                (name, pool.submit(run_train, name))
                for name in classificators_list
            ]
            wait([future for _, future in futures])
    finally:
        # End-of-job barrier: no build returns (or fails) with writes
        # still in flight; a failed write-back fails that MEMBER.
        write_failures = writer.barrier() if writer is not None else []
    return _collect_outcomes(
        classificators_list, futures, write_failures, handle
    )


def _collect_outcomes(
    classificators_list, futures, write_failures, handle
) -> list[dict]:
    """Fold per-classifier futures + write-back failures into the
    build's result — the partial-results contract: ONE failed member
    no longer fails the whole job. Outcomes:

    - all succeeded → the metadata list, as ever;
    - any cancelled → the cancellation re-raises (job CANCELLED);
    - all failed → the single member's exception re-raises verbatim
      (single-classifier builds keep their reference-parity 500
      bodies), several failures raise one aggregate;
    - mixed → the successes return, the job FINISHES, and the record
      carries ``detail.result = "finished_partial"`` with a per-name
      status map (surfaced by GET /jobs/<name> and the /wait body).

    Failed members are journaled (``status="failed"``) so a resumed
    run skips them instead of re-running a permanent failure."""
    succeeded: list[dict] = []
    errors: dict[str, BaseException] = {}
    cancelled: Optional[BaseException] = None
    write_failed = dict(write_failures)
    for name, future in futures:
        try:
            result = future.result()
        except JobCancelledError as interruption:
            cancelled = interruption
            continue
        except BaseException as error:  # noqa: BLE001 — folded below
            errors[name] = error
            continue
        if name in write_failed:
            # compute finished, but the overlapped write-back failed:
            # this member's outputs never landed
            errors[name] = write_failed[name]
            continue
        succeeded.append(result)
    if cancelled is not None:
        raise cancelled
    for name, error in errors.items():
        if isinstance(error, _ResumedMemberFailure):
            continue  # already journaled by the pre-crash run
        traceback.print_exception(type(error), error, error.__traceback__)
        if handle is not None:
            handle.progress(
                classificator=name,
                status="failed",
                error=_member_error(error),
            )
    if not errors:
        return succeeded
    statuses = {
        name: (
            {"status": "failed", "error": _member_error(errors[name])}
            if name in errors
            else {"status": "finished"}
        )
        for name in classificators_list
    }
    if not succeeded:
        if len(errors) == 1:
            raise next(iter(errors.values()))
        raise RuntimeError(
            "all classifiers failed: "
            + "; ".join(
                f"{name}: {_member_error(error)}"
                for name, error in errors.items()
            )
        )
    if handle is not None:
        handle.annotate(result="finished_partial", classifiers=statuses)
    return succeeded


def _member_error(error: BaseException) -> str:
    if isinstance(error, _ResumedMemberFailure):
        return str(error)  # already formatted by the pre-crash run
    return f"{type(error).__name__}: {error}"


def predict_with_model(
    store: DocumentStore,
    checkpoint_path: str,
    training_filename: str,
    test_filename: str,
    preprocessor_code: str,
    prediction_filename: str,
    mesh: Optional[Mesh] = None,
    write_outputs: bool = True,
) -> dict:
    """Serve predictions from a saved checkpoint — no refit.

    Loads the artifact :func:`train_one` persisted, re-runs the same
    preprocessor over the same (training, test) frames — the training
    frame is required because preprocessor state is derived from it
    (StringIndexer category order, assembler column lists, imputation
    stats); feeding the test frame in its place would silently permute
    or reshape features. Then predicts and writes the prediction
    collection in the same shape build_model produces. This is the
    resume path the reference cannot offer: its fitted models die with
    the request (model_builder.py:232-247)."""
    from learningorchestra_tpu.ml.checkpoint import load_model

    model = load_model(checkpoint_path, mesh=mesh)
    training_df = load_dataframe(store, training_filename)
    testing_df = load_dataframe(store, test_filename)
    out = run_preprocessor(preprocessor_code, training_df, testing_df)

    metadata = {
        "filename": prediction_filename,
        "model_checkpoint": checkpoint_path,
        ROW_ID: 0,
    }
    return _predict_and_write(
        store,
        model,
        out["features_testing"],
        prediction_filename,
        metadata,
        PhaseTimer(),
        write_outputs,
    )
