"""Shared estimator contract and device data preparation.

Design notes (TPU-first):

- Features travel as one dense ``(rows, features)`` float32 matrix —
  the MXU wants large batched matmuls, not per-row documents.
- Rows are padded to the mesh's ``data``-axis size and carried with a
  validity mask (static shapes; XLA compiles one program per padded
  shape). Every reduction in every estimator is mask-weighted, so
  padding never biases a fit.
- ``mesh=None`` means "all visible devices on the data axis" via the
  same code path: single-chip is just a 1-wide mesh.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import Future
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from learningorchestra_tpu.ml import binning
from learningorchestra_tpu.parallel.mesh import default_mesh
from learningorchestra_tpu.parallel.sharding import shard_rows
from learningorchestra_tpu.telemetry import tracing as _tracing

# The model-builder request contract (reference:
# microservices/model_builder_image/model_builder.py:151-157,287-291).
CLASSIFIER_NAMES = ("lr", "dt", "rf", "gb", "nb")


def resolve_mesh(mesh: Optional[Mesh]) -> Mesh:
    return mesh if mesh is not None else default_mesh()


# Multiplier on every per-estimator segment budget, read ONCE at import:
# per-request env reads could desynchronize SPMD dispatch counts across
# a multi-host mesh, so the knob is process-lifetime constant and must
# be set identically on every host (deploy/README.md env contract).
try:
    _PROGRAM_BUDGET_SCALE = float(
        # lo: allow[LO305] module-level read-once by design (see above)
        os.environ.get("LO_PROGRAM_ROW_STEPS", "1") or "1"
    )
except ValueError as error:
    raise ValueError(
        "LO_PROGRAM_ROW_STEPS must be a number, got "
        # lo: allow[LO305] error-message echo of the same knob
        f"{os.environ.get('LO_PROGRAM_ROW_STEPS')!r}"
    ) from error


def largest_divisor(total: int, cap: int, multiple_of: int = 1) -> int:
    """Largest divisor of ``total`` that is <= ``cap`` and a multiple of
    ``multiple_of``; falls back to ``multiple_of`` (assumed to divide
    ``total``) when no divisor fits under the cap."""
    best = 0
    for candidate in range(multiple_of, total + 1, multiple_of):
        if total % candidate == 0 and candidate <= cap:
            best = candidate
    return best or multiple_of


def segment_steps(
    total: int, rows: int, row_steps_budget: float, features: int = 16
) -> int:
    """Steps per device program so one XLA execution stays short.

    Iterative fits (L-BFGS iterations, boosting rounds, forest trees)
    are dispatched as a handful of medium programs instead of one long
    one. The segment is the unit of everything that happens BETWEEN
    programs: crash-resume saves progress per segment (ml/progress.py),
    so it bounds the work a killed or preempted job loses; the L-BFGS
    convergence check and job cancellation fire at segment boundaries;
    and a runtime that limits how long one execution may run (a shared
    fleet's watchdog) never sees a minutes-long program.
    ``row_steps_budget``
    is the per-program budget in row*steps at a 16-feature reference
    width (per-step cost scales with the feature count for both matmul
    and histogram passes, so ``features`` rescales the budget); the
    result is the largest divisor of ``total`` within budget, so every
    segment has the same static shape and compiles exactly once.
    ``LO_PROGRAM_ROW_STEPS`` multiplies all budgets (larger segments:
    fewer dispatches, coarser resume points); it is read once per
    process so every host of a multi-host mesh computes the same
    segmentation.
    """
    row_steps_budget *= _PROGRAM_BUDGET_SCALE
    if total <= 1 or rows <= 0:
        return max(total, 1)
    cost_rows = rows * max(features, 1) / 16
    target = max(1, int(row_steps_budget / cost_rows))
    if target >= total:
        return total
    return largest_divisor(total, target)


class DeviceMatrix:
    """A feature matrix already padded + row-sharded on the mesh.

    The builder shards the shared test/eval matrices ONCE and every
    classifier predicts against the same device buffers —
    ``prepare_xy`` passes them straight through, so N models cost one
    host→device transfer, not N (the tail the reference pays per
    evaluator, model_builder.py:205-224). The training matrix the N
    models fit from is one too (:class:`TrainingInput`)."""

    __slots__ = ("data", "mask", "rows", "mesh")

    def __init__(self, data: jax.Array, mask: jax.Array, rows: int, mesh: Mesh):
        self.data = data
        self.mask = mask
        self.rows = rows
        self.mesh = mesh

    def __len__(self) -> int:
        return self.rows


def shard_matrix(X: np.ndarray, mesh: Optional[Mesh] = None) -> DeviceMatrix:
    """Pad + row-shard a feature matrix once, for reuse across models."""
    mesh = resolve_mesh(mesh)
    X = np.asarray(X)
    X_dev, mask = shard_rows(X, mesh, dtype=np.float32)
    return DeviceMatrix(X_dev, mask, len(X), mesh)


class DeviceLabels:
    """A label vector already padded + row-sharded, with its class count
    captured host-side (the scatter in the device metrics needs a static
    bound). Shared across classifier threads like :class:`DeviceMatrix`."""

    __slots__ = ("data", "num_classes", "mesh")

    def __init__(self, data: jax.Array, num_classes: int, mesh: Mesh):
        self.data = data
        self.num_classes = num_classes
        self.mesh = mesh


def shard_labels(y: np.ndarray, mesh: Optional[Mesh] = None) -> DeviceLabels:
    mesh = resolve_mesh(mesh)
    y = np.asarray(y)
    y_dev, _ = shard_rows(y, mesh, dtype=np.int32)
    return DeviceLabels(y_dev, infer_num_classes(y), mesh)


class TrainingInput:
    """What every fit of a build derives from its training frame, made
    once and shared by the build's fits: the host matrix and labels,
    their class count, ONE padded, row-sharded device copy of both
    (:meth:`device`) and the bin thresholds of that copy, one table per
    ``max_bins`` (:meth:`thresholds`).

    The device copy and each table are made by the first fit that asks
    for them; the fits that ask meanwhile wait for that result, or its
    error, after which nothing is kept and the next ask tries again. A
    fit handed a numpy matrix makes an input of its own (:meth:`of`),
    so a library call and a build run the same code. The shared arrays
    are read-only to the fits: none donates or writes them.

    Deliberately not in the content-addressed device cache
    (core/devcache.py): each build ships its training matrix once and
    the builder lets the copy go when its fits are done
    (:meth:`release`). Users rebuild after changing the coding, so a
    build's matrix is new content; hashing it would put a linear host
    pass back on the build's path, and a kept copy would hold HBM
    between builds."""

    def __init__(self, X, y, mesh: Optional[Mesh] = None):
        self.X = np.asarray(X)
        self.y = np.asarray(y)
        self.mesh = resolve_mesh(mesh)
        self.num_classes = infer_num_classes(self.y)
        self._lock = threading.Lock()
        self._made: dict = {}

    @classmethod
    def of(cls, X, y, mesh: Mesh) -> "TrainingInput":
        """The input a fit on ``mesh`` works from: ``X`` itself where it
        is one (on another mesh, its host arrays again), else the input
        of the host matrix ``X`` and labels ``y``."""
        if isinstance(X, TrainingInput):
            return X if X.mesh is mesh else cls(X.X, X.y, mesh)
        return cls(X, y, mesh)

    @property
    def rows(self) -> int:
        return len(self.X)

    @property
    def features(self) -> int:
        return self.X.shape[1]

    def __len__(self) -> int:
        return self.rows

    def __getitem__(self, rows) -> "TrainingInput":
        """The input of some of these rows, with a device copy of its
        own: for a wrapper that hands a fit part of a build's rows."""
        return TrainingInput(self.X[rows], self.y[rows], self.mesh)

    def _claim(self, key) -> tuple[Future, bool]:
        """The future that holds ``key``'s result, and whether the
        caller is the one to make it."""
        with self._lock:
            made = self._made.get(key)
            if made is not None:
                return made, False
            made = self._made[key] = Future()
            return made, True

    def _settle(self, key, made: Future, make):
        try:
            value = make()
        except BaseException as error:
            with self._lock:
                self._made.pop(key, None)
            made.set_exception(error)
            raise
        made.set_result(value)
        return value

    def device(self) -> tuple[DeviceMatrix, DeviceLabels]:
        """The device copy of the matrix and the labels. The fit that
        makes it does so under one ``h2d:train`` span (``rows``,
        ``copies`` 1, the bytes ``shard_rows`` stamps), which ends when
        the arrays have landed: inside it ``shard_rows``' ``h2d:stage``
        and ``h2d:put`` for the matrix, the same for the labels, then
        ``h2d:land``, the wait for the transfer. The others wait under
        ``fit:input``."""
        made, mine = self._claim("device")
        if not mine:
            with _tracing.span("fit:input", rows=self.rows):
                return made.result()
        return self._settle("device", made, self._copy)

    def _copy(self) -> tuple[DeviceMatrix, DeviceLabels]:
        with _tracing.span("h2d:train", rows=self.rows, copies=1):
            matrix = shard_matrix(self.X, self.mesh)
            labels = shard_labels(self.y, self.mesh)
            _tracing.device_wait(
                "h2d:land", (matrix.data, matrix.mask, labels.data)
            )
        return matrix, labels

    def thresholds(
        self, matrix: DeviceMatrix, max_bins: int
    ) -> tuple[np.ndarray, int]:
        """``(thresholds, passes)``: the quantile thresholds of
        ``matrix``, this input's device copy, for ``max_bins`` bins
        (read-only). The fit that runs the device pass gets ``passes``
        1, the ones that wait for it 0."""

        def run() -> np.ndarray:
            thresholds = binning.device_thresholds(
                matrix.data, matrix.mask, max_bins
            )
            thresholds.flags.writeable = False
            return thresholds

        made, mine = self._claim(("thresholds", max_bins))
        if not mine:
            return made.result(), 0
        return self._settle(("thresholds", max_bins), made, run), 1

    def release(self) -> None:
        """Let the device copy go (its HBM is freed with the last
        reference); a later :meth:`device` makes it again."""
        with self._lock:
            self._made.pop("device", None)


def prepare_xy(
    X, y: Optional[np.ndarray], mesh: Mesh
) -> tuple[jax.Array, Optional[jax.Array], jax.Array]:
    """Pad + row-shard features (float32), labels (int32) and the
    validity mask over the mesh's data axis. A :class:`DeviceMatrix`
    sharded on the same mesh passes through without any transfer.
    A forward's input (a serve batch, a warm-up's rows, PCA's matrix)
    comes this way; a fit's comes through :class:`TrainingInput`."""
    if isinstance(X, DeviceMatrix):
        if X.mesh is mesh:
            y_dev = None
            if y is not None:
                with _tracing.span("h2d:input", rows=len(y)):
                    y_dev, _ = shard_rows(np.asarray(y), mesh, dtype=np.int32)
            return X.data, y_dev, X.mask
        # mesh mismatch: fall back through host memory
        X = np.asarray(jax.device_get(X.data))[: X.rows]
    # one span where the input crosses to the device: shard_rows'
    # account_h2d stamps the bytes on it
    with _tracing.span("h2d:input", rows=len(X)):
        X_dev, mask = shard_rows(np.asarray(X), mesh, dtype=np.float32)
        y_dev = None
        if y is not None:
            y_dev, _ = shard_rows(np.asarray(y), mesh, dtype=np.int32)
    return X_dev, y_dev, mask


def infer_num_classes(y: np.ndarray) -> int:
    """Labels are class indices 0..C-1 (the MLlib convention: label is a
    double holding an index, reference docs/model_builder.md)."""
    return int(np.max(y)) + 1 if len(y) else 1


class FittedModel:
    """Base for fitted models: numpy (or :class:`DeviceMatrix`) in,
    numpy out, device inside.

    Subclasses implement ``_device_eval(X) -> (labels, probs, mask)``
    (all padded, device-resident); the base class provides host-facing
    predict/evaluate built on it with the minimum number of device
    round trips — one forward pass serves labels, probabilities AND
    on-device metrics (the reference runs two JVM evaluators plus a
    collect over the same predictions, model_builder.py:205-247)."""

    mesh: "Mesh"

    def _device_eval(self, X):
        raise NotImplementedError

# Every current model's labels are argmax(probs) (softmax/posterior/
# ensemble-mean are all argmax-monotonic), so the host can rebuild them
# from the probabilities and the label buffer never has to travel.
    labels_from_probs = True

    def _transfer(
        self, labels, probs, n: int, scalars: tuple = ()
    ) -> tuple[np.ndarray, np.ndarray, tuple]:
        """ONE blocking device→host transfer of a forward pass, plus any
        ``scalars`` batched into the same trip — each device→host
        fetch is a synchronisation, so every entry point funnels
        through here. Labels are rebuilt host-side when they are argmax(probs)
        (``labels_from_probs``), so the label buffer never travels.
        Multi-host arrays gather via ``fetch``. In the active trace (a
        no-op outside one) the wait for the forward is an
        ``eval:device_wait`` span and the blocking transfer after it a
        ``d2h`` span, so the device→host tail shows up in
        ``/jobs/<name>/trace`` next to the ``h2d`` spans the data plane
        emits."""
        from learningorchestra_tpu.telemetry import profile as _profile

        # the forward and whatever is queued ahead of it on the device
        # finish here, so that the d2h span below times the copy alone
        wanted = (probs,) if self.labels_from_probs else (labels, probs)
        _tracing.device_wait("eval:device_wait", wanted + tuple(scalars))
        with _tracing.span("d2h:predictions", rows=n):
            if jax.process_count() > 1:
                from learningorchestra_tpu.parallel.multihost import fetch

                probs_np = np.asarray(fetch(probs))[:n]
                labels_np = (
                    np.argmax(probs_np, axis=1)
                    if self.labels_from_probs
                    else np.asarray(fetch(labels))[:n]
                )
                fetched = jax.device_get(tuple(scalars)) if scalars else ()
                _profile.account_d2h(probs_np.nbytes + labels_np.nbytes)
                return labels_np, probs_np, tuple(fetched)
            if self.labels_from_probs:
                out = jax.device_get((probs,) + tuple(scalars))
                probs_np = np.asarray(out[0])[:n]
                _profile.account_d2h(probs_np.nbytes)
                return np.argmax(probs_np, axis=1), probs_np, tuple(out[1:])
            out = jax.device_get((labels, probs) + tuple(scalars))
            _profile.account_d2h(out[0].nbytes + out[1].nbytes)
            return (
                np.asarray(out[0])[:n],
                np.asarray(out[1])[:n],
                tuple(out[2:]),
            )

    def _eval(self, X) -> tuple[np.ndarray, np.ndarray]:
        with _tracing.span("eval:enqueue"):
            labels, probs, _ = self._device_eval(X)
        labels_np, probs_np, _ = self._transfer(labels, probs, len(X))
        return labels_np, probs_np

    def predict(self, X) -> np.ndarray:
        return self._eval(X)[0]

    def predict_proba(self, X) -> np.ndarray:
        return self._eval(X)[1]

    def predict_both(self, X) -> tuple[np.ndarray, np.ndarray]:
        """``(labels, probabilities)`` from ONE forward pass — calling
        predict then predict_proba would run the program twice."""
        return self._eval(X)

    def _device_metrics(self, X, y_true):
        """Dispatch forward + on-device confusion metrics; returns the
        unfetched ``(accuracy, weighted_f1)`` device scalars plus the
        forward outputs so callers can batch the host transfer."""
        from learningorchestra_tpu.ml.evaluation import masked_metrics
        from learningorchestra_tpu.parallel.sharding import shard_rows

        labels, probs, mask = self._device_eval(X)
        if isinstance(y_true, DeviceLabels):  # pre-sharded by the builder
            y_dev = y_true.data
            num_classes = max(int(probs.shape[-1]), y_true.num_classes)
        else:
            num_classes = max(int(probs.shape[-1]), infer_num_classes(y_true))
            y_dev, _ = shard_rows(np.asarray(y_true), self.mesh, dtype=np.int32)
        accuracy, weighted_f1 = masked_metrics(y_dev, labels, mask, num_classes)
        return accuracy, weighted_f1, labels, probs

    def evaluate(self, X, y_true: np.ndarray) -> tuple[float, float]:
        """``(accuracy, weighted_f1)`` with the confusion matrix built
        ON DEVICE from the forward pass — one dispatch, two scalars
        back; predictions never round-trip through host memory."""
        accuracy, weighted_f1, _, _ = self._device_metrics(X, y_true)
        # one transfer for both scalars
        accuracy, weighted_f1 = jax.device_get((accuracy, weighted_f1))
        return float(accuracy), float(weighted_f1)

    def evaluate_predict(
        self, X_eval, y_eval, X_test
    ) -> tuple[float, float, np.ndarray, np.ndarray]:
        """Metrics on the eval split AND ``(labels, probabilities)`` on
        the test split in ONE blocking device→host transfer — the
        builder's per-classifier tail collapsed from three round trips
        (evaluate scalars, predict labels, predict probs) to one. When
        ``X_test is X_eval`` (the documented product path evaluates on
        the test frame, reference model_builder.py:205-224 runs its two
        evaluators AND collect() over that same frame) the forward pass
        itself runs once."""
        # eval:enqueue: the forward and the metrics handed to the device;
        # blocks while the device's queue is full (fit:enqueue, ml/trees.py)
        with _tracing.span("eval:enqueue"):
            accuracy, weighted_f1, labels_e, probs_e = self._device_metrics(
                X_eval, y_eval
            )
            if X_test is X_eval:
                labels_t, probs_t = labels_e, probs_e
            else:
                labels_t, probs_t, _ = self._device_eval(X_test)
        labels_np, probs_np, (accuracy, weighted_f1) = self._transfer(
            labels_t, probs_t, len(X_test), (accuracy, weighted_f1)
        )
        return float(accuracy), float(weighted_f1), labels_np, probs_np

    def device_state(self) -> list:
        """The fitted model's device arrays (for block_until_ready —
        honest fit-phase attribution under async dispatch)."""
        leaves = jax.tree.leaves(vars(self))
        return [leaf for leaf in leaves if isinstance(leaf, jax.Array)]


def make_classifier(name: str, mesh: Optional[Mesh] = None):
    """The classifier switcher (reference model_builder.py:151-157)."""
    from learningorchestra_tpu.ml.logistic import LogisticRegression
    from learningorchestra_tpu.ml.naive_bayes import NaiveBayes
    from learningorchestra_tpu.ml.trees import (
        DecisionTreeClassifier,
        GBTClassifier,
        RandomForestClassifier,
    )

    switcher = {
        "lr": LogisticRegression,
        "dt": DecisionTreeClassifier,
        "rf": RandomForestClassifier,
        "gb": GBTClassifier,
        "nb": NaiveBayes,
    }
    if name not in switcher:
        raise KeyError(name)
    return switcher[name](mesh=mesh)
