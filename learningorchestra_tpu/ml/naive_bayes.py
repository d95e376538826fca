"""Multinomial naive Bayes — a single fused matmul fit.

Replaces Spark MLlib's ``NaiveBayes`` (reference:
microservices/model_builder_image/model_builder.py:13,156; MLlib default
``modelType="multinomial"``, ``smoothing=1.0``). Requires non-negative
features, like MLlib.

TPU shape: the entire fit is ``one_hot(y)ᵀ @ X`` — one (classes, rows) ×
(rows, features) matmul on the MXU — plus two log-normalizations. On a
row-sharded mesh the matmul's row contraction IS the cross-chip
reduction; XLA lowers it to a psum over ICI. This is the op the
reference spent 41.87 s of Spark JVM time on for 891 Titanic rows
(BASELINE.md).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from learningorchestra_tpu.ml.base import (
    FittedModel,
    TrainingInput,
    prepare_xy,
    resolve_mesh,
)
from learningorchestra_tpu.telemetry import tracing as _tracing


@partial(jax.jit, static_argnames=("num_classes",))
def _fit(X, y, mask, num_classes: int, smoothing):
    one_hot = jax.nn.one_hot(y, num_classes, dtype=jnp.float32) * mask[:, None]
    class_feature_sums = one_hot.T @ X                      # (C, F) on the MXU
    class_counts = one_hot.sum(axis=0)                      # (C,)
    smoothed = class_feature_sums + smoothing
    theta = jnp.log(smoothed) - jnp.log(smoothed.sum(axis=1, keepdims=True))
    prior = jnp.log(class_counts) - jnp.log(mask.sum())
    return theta, prior


@jax.jit
def _forward(theta, prior, X):
    joint = X @ theta.T + prior                             # (N, C)
    probs = jax.nn.softmax(joint)
    return jnp.argmax(joint, axis=1), probs


class NaiveBayesModel(FittedModel):
    def __init__(self, theta, prior, mesh: Mesh):
        self.theta = theta
        self.prior = prior
        self.mesh = mesh

    def _device_eval(self, X):
        X_dev, _, mask = prepare_xy(X, None, self.mesh)
        labels, probs = _forward(self.theta, self.prior, X_dev)
        return labels, probs, mask


class NaiveBayes:
    def __init__(self, smoothing: float = 1.0, mesh: Optional[Mesh] = None):
        self.smoothing = smoothing
        self.mesh = resolve_mesh(mesh)

    def fit(self, X, y=None) -> NaiveBayesModel:
        data = TrainingInput.of(X, y, self.mesh)
        if np.nanmin(data.X) < 0:
            raise ValueError(
                "NaiveBayes requires non-negative features (MLlib contract)"
            )
        matrix, labels = data.device()
        with _tracing.span("fit:enqueue"):  # see ml/trees.py
            theta, prior = _fit(
                matrix.data,
                labels.data,
                matrix.mask.astype(jnp.float32),
                num_classes=data.num_classes,
                smoothing=jnp.float32(self.smoothing),
            )
            return NaiveBayesModel(theta, prior, self.mesh)
