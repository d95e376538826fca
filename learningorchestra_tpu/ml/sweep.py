"""Batched-fit entry points: many fits as ONE vmap-across-jobs dispatch.

Two workloads share this machinery:

- **Hyperparameter sweeps** (``POST /models/sweep``): a λ grid over
  :mod:`~learningorchestra_tpu.ml.logistic` or a depth grid over
  :mod:`~learningorchestra_tpu.ml.trees`, fitted as one ``vmap`` over
  the grid axis with per-point metrics and the argmax checkpoint
  published through the same atomic ``os.replace`` path the builder
  uses — so the serving registry (serve/registry.py) picks the winner
  up like any other build. A scenario the reference never had.
- **Job coalescing** (sched/coalesce.py): a flood of small
  single-classifier builds from many users fuses into one dispatch —
  every member's (X, y, λ) tuple becomes one more slice on the same
  job axis a sweep uses for its grid points.

The fused program's job axis pads to the shared quarter-octave shape
grid (utils/shapegrid.py) with a fixed floor, then aligns to the mesh's
data-axis size so the axis always partitions evenly across devices (the
pjit idiom: jobs are embarrassingly parallel, so sharding the job axis
inserts ZERO collectives — matched in/out specs, no cross-slice
reduction anywhere). Dummy slots replicate slot 0 rather than holding
zeros (an all-zero member would drive 0/0 NaNs through its lanes).

Reproducibility contract (the coalescer's acceptance bar): a vmap
slice's result depends only on its own inputs, and two dispatches padded
to the SAME job-axis width run the SAME XLA program — so a job fused
into a batch of N is bit-identical to the same job run alone whenever
both land on one grid value (which the fixed pad floor guarantees for
small batches). Batched fits run their full iteration budget — the solo
path's plateau early-exit is per-member host control flow that would
make one member's stopping point depend on its neighbors'.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.core.store import ROW_ID, DocumentStore
from learningorchestra_tpu.ml.base import (
    infer_num_classes,
    resolve_mesh,
    segment_steps,
)
from learningorchestra_tpu.ml.binning import MAX_BINS, apply_bins, make_thresholds
from learningorchestra_tpu.ml.evaluation import masked_metrics
from learningorchestra_tpu.ml.logistic import (
    _LR_ROW_ITERS_BUDGET,
    _fit_segment,
    _forward,
    _lbfgs_state,
    _lr_standardize,
)
from learningorchestra_tpu.ml.trees import (
    _dt_fit,
    _ensemble_forward,
    _heap_thresholds,
)
from learningorchestra_tpu.parallel.mesh import DATA_AXIS, data_size
from learningorchestra_tpu.parallel.sharding import pad_rows
from learningorchestra_tpu.sched.cancel import check_cancelled
from learningorchestra_tpu.telemetry import tracing as _tracing
from learningorchestra_tpu.utils.shapegrid import grid_size, padded_indices

SWEEP_CLASSIFIERS = ("lr", "dt")

# Fused job axes pad to grid_size(n, floor=_JOB_PAD_FLOOR), then align
# to the mesh's data-axis size. The floor is the MicroBatcher trick at
# job granularity: every batch of <= 8 jobs runs the ONE compiled
# 8-slot program (bit-reproducible across batch sizes), larger batches
# ride the quarter-octave grid.
_JOB_PAD_FLOOR = 8

# One fused dispatch's job axis is capped so a large grid over a large
# dataset cannot demand (points x rows x features) HBM in one program;
# grids past the cap chain through several fused dispatches (still one
# compile, ~points/cap executions — nothing like one dispatch per fit).
_MAX_FUSED_SLICES = 128

# Grids past this are a misuse of the synchronous sweep route, not a
# bigger batch (the job axis multiplies every member's arrays).
MAX_GRID_POINTS = 1024

_DEFAULT_MAX_ITER = 100  # MLlib maxIter default, like the solo LR path


# --------------------------------------------------------------------------
# Grid validation (the route's 406 surface)
# --------------------------------------------------------------------------

def validate_grid(kind: str, grid) -> list[dict]:
    """Normalize a sweep grid or raise ``ValueError`` with the offending
    entry. ``lr`` grids sweep ``reg_param`` (λ >= 0); ``dt`` grids sweep
    ``max_depth`` (int in [1, 12] — the tree heap is 2^depth arrays)."""
    if kind not in SWEEP_CLASSIFIERS:
        raise ValueError(
            f"classificator {kind!r} is not sweepable "
            f"(have: {SWEEP_CLASSIFIERS})"
        )
    if not isinstance(grid, list) or not grid:
        raise ValueError("grid must be a non-empty list of points")
    if len(grid) > MAX_GRID_POINTS:
        raise ValueError(
            f"grid has {len(grid)} points (max {MAX_GRID_POINTS})"
        )
    normalized: list[dict] = []
    for entry in grid:
        if not isinstance(entry, dict):
            raise ValueError(f"grid points must be objects, got {entry!r}")
        if kind == "lr":
            value = entry.get("reg_param")
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                raise ValueError(
                    f"lr grid points need a numeric reg_param, got {entry!r}"
                )
            if not np.isfinite(value) or value < 0:
                raise ValueError(f"reg_param must be finite and >= 0: {entry!r}")
            normalized.append({"reg_param": float(value)})
        else:
            value = entry.get("max_depth")
            if isinstance(value, bool) or not isinstance(value, int):
                raise ValueError(
                    f"dt grid points need an integer max_depth, got {entry!r}"
                )
            if not 1 <= value <= 12:
                raise ValueError(f"max_depth must be in [1, 12]: {entry!r}")
            normalized.append({"max_depth": int(value)})
    return normalized


# --------------------------------------------------------------------------
# Member preparation (host work, BEFORE the device queue)
# --------------------------------------------------------------------------

def prepare_member(
    kind: str,
    X_train: np.ndarray,
    y_train: np.ndarray,
    X_eval: np.ndarray,
    y_eval: np.ndarray,
    grid: list[dict],
    mesh: Optional[Mesh] = None,
    max_iter: int = _DEFAULT_MAX_ITER,
) -> tuple[tuple, dict]:
    """Host-side prep for one coalescible fit/sweep job: pad + dtype the
    arrays and derive the compatibility ``key`` — everything the fused
    program's shape depends on, so two members with equal keys stack on
    one job axis. Runs on the submitting thread (prep must precede the
    device queue: a leader can only stack payloads that already exist).

    Deliberately does NOT validate finiteness: a NaN-poisoned member
    must fail INSIDE the fused dispatch (alone, neighbors unaffected) —
    that isolation is part of the coalescer's contract and is tested.
    """
    mesh = resolve_mesh(mesh)
    grid = validate_grid(kind, grid)
    if not isinstance(max_iter, int) or max_iter < 1:
        raise ValueError(f"max_iter must be an integer >= 1, got {max_iter!r}")
    X_train = np.asarray(X_train)
    y_train = np.asarray(y_train)
    X_eval = np.asarray(X_eval)
    y_eval = np.asarray(y_eval)
    if X_train.ndim != 2 or X_eval.ndim != 2:
        raise ValueError("feature matrices must be 2-D")
    if X_train.shape[1] != X_eval.shape[1]:
        raise ValueError("train/eval feature widths differ")
    num_classes = max(infer_num_classes(y_train), infer_num_classes(y_eval))
    multiple = data_size(mesh)
    X_pad, mask = pad_rows(X_train, multiple)
    y_pad, _ = pad_rows(y_train, multiple)
    Xe_pad, mask_e = pad_rows(X_eval, multiple)
    ye_pad, _ = pad_rows(y_eval, multiple)
    payload = {
        "kind": kind,
        "grid": grid,
        # scanned HERE on the submitting thread (parallel across
        # requests), verdict carried to the fused dispatch where the
        # member fails ALONE (run_group) — scanning there instead would
        # serialize O(members x rows x features) host work on the
        # width-1 device lane
        "finite": bool(
            np.isfinite(X_train).all() and np.isfinite(X_eval).all()
        ),
        "X": X_pad.astype(np.float32),
        "y": y_pad.astype(np.int32),
        "mask": mask.astype(np.float32),
        "X_eval": Xe_pad.astype(np.float32),
        "y_eval": ye_pad.astype(np.int32),
        "mask_eval": mask_e.astype(np.float32),
        "rows": int(len(X_train)),
        "num_classes": num_classes,
        "max_iter": int(max_iter),
    }
    if kind != "lr":  # LR standardises on the device (_run_lr_chunk)
        payload["thresholds"] = make_thresholds(X_train, MAX_BINS).astype(
            np.float32
        )
    key = (
        "sweep",
        kind,
        int(X_pad.shape[0]),
        int(Xe_pad.shape[0]),
        int(X_pad.shape[1]),
        num_classes,
        int(max_iter) if kind == "lr" else MAX_BINS,
        "f32",
        _mesh_signature(mesh),
    )
    return key, payload


def _mesh_signature(mesh: Mesh) -> tuple:
    from learningorchestra_tpu.core.devcache import mesh_signature

    return mesh_signature(mesh)


# --------------------------------------------------------------------------
# The fused programs
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("iters", "fresh"))
def _lr_fused_segment(
    params, states, Xs, ys, masks, l2s, iters: int, fresh: bool
):
    """``iters`` L-BFGS iterations for EVERY slice of the job axis as
    one program — the solo fit's segment (ml/logistic.py) under vmap,
    optimizer state carried per slice across segment boundaries
    (``fresh``: the first segment, which seeds value and gradient)."""

    def one(p, s, X, y, m, l2):
        return _fit_segment(p, s, X, y, m, iters, l2, fresh=fresh)

    return jax.vmap(one)(params, states, Xs, ys, masks, l2s)


_standardize_members = jax.jit(jax.vmap(_lr_standardize))


@partial(jax.jit, static_argnames=("num_classes",))
def _lr_fused_eval(params, Xe, means, scales, ye, masks_e, num_classes: int):
    """Per-slice forward + on-device confusion metrics: one dispatch
    yields every point's (accuracy, weighted F1). The forward IS the
    product path's (logistic._forward under vmap) — not a re-typed
    copy that could drift from what the checkpoint will serve."""

    def one(p, X, mean, scale, y, m):
        labels, _ = _forward(p, X, mean, scale)
        return masked_metrics(y, labels, m, num_classes)

    return jax.vmap(one)(params, Xe, means, scales, ye, masks_e)


@partial(jax.jit, static_argnames=("num_classes", "max_depth", "max_bins"))
def _dt_fused(
    Xs, ys, ws, thresholds, Xe, ye, we,
    num_classes: int, max_depth: int, max_bins: int,
):
    """Bin + grow + evaluate one decision tree PER SLICE — the whole
    histogram-tree pipeline (ml/trees.py) under vmap. Depth is a static
    program shape, so a depth grid groups points by depth and runs one
    fused program per distinct depth (each still a batch over the job
    axis, never one dispatch per point)."""

    def one(X, y, w, th, Xev, yev, wev):
        bins = apply_bins(X, th)
        features_heap, bins_heap, leaf_probs = _dt_fit(
            bins, y, w, num_classes, max_depth, max_bins
        )
        thresholds_heap = _heap_thresholds(features_heap, bins_heap, th)
        probs = _ensemble_forward(
            Xev,
            features_heap[None],
            thresholds_heap[None],
            leaf_probs[None],
            max_depth,
        )
        labels = jnp.argmax(probs, axis=1)
        accuracy, weighted_f1 = masked_metrics(yev, labels, wev, num_classes)
        return features_heap, thresholds_heap, leaf_probs, accuracy, weighted_f1

    return jax.vmap(one)(Xs, ys, ws, thresholds, Xe, ye, we)


def _job_axis(n: int, mesh: Mesh) -> tuple[int, NamedSharding]:
    """Padded slot count and sharding for a fused job axis: grid floor,
    then aligned to the data-axis size so the axis ALWAYS partitions
    evenly — slices are independent, so this is collective-free SPMD."""
    devices = data_size(mesh)
    target = grid_size(n, _JOB_PAD_FLOOR)
    target = ((target + devices - 1) // devices) * devices
    return target, NamedSharding(mesh, P(DATA_AXIS))


def _stack(arrays: list[np.ndarray], sharding) -> jax.Array:
    """Stack per-slice host arrays along a new job axis and place it
    job-sharded (callers build the padded slot list via
    ``padded_indices``: dummy slots replicate slot 0)."""
    return jax.device_put(np.stack(arrays), sharding)


# --------------------------------------------------------------------------
# The group runner (executed ONCE per fused batch by the coalescer leader)
# --------------------------------------------------------------------------

def group_runner(mesh: Optional[Mesh] = None):
    """The coalescer's runner for sweep/fit members: ``(payloads) ->
    [outcome, ...]`` with the per-member isolation contract from
    sched/coalesce.py (an outcome is ``("ok", result)`` or
    ``("error", exception)``)."""
    mesh = resolve_mesh(mesh)

    def run(payloads: list) -> list:
        return run_group(payloads, mesh)

    return run


def run_group(payloads: list, mesh: Mesh) -> list:
    outcomes: list = [None] * len(payloads)
    live: list[int] = []
    for index, payload in enumerate(payloads):
        # per-member validation verdict (computed at prepare_member on
        # the submitting thread): a poisoned member fails ALONE — NaN
        # features would otherwise silently NaN its fitted params
        if not payload.get("finite", True):
            outcomes[index] = (
                "error",
                ValueError(
                    "non-finite features in coalesced member "
                    f"{index} — member failed, neighbors unaffected"
                ),
            )
        else:
            live.append(index)
    if not live:
        return outcomes
    kind = payloads[live[0]]["kind"]
    # one flat slice list: (member, point) pairs — a 100-λ sweep is one
    # member with 100 slices, 64 coalesced small builds are 64 members
    # with one slice each; the fused program cannot tell the difference
    slices = [
        (member, point)
        for member in live
        for point in range(len(payloads[member]["grid"]))
    ]
    per_point: dict[tuple[int, int], dict] = {}
    for start in range(0, len(slices), _MAX_FUSED_SLICES):
        chunk = slices[start : start + _MAX_FUSED_SLICES]
        if kind == "lr":
            _run_lr_chunk(payloads, chunk, per_point, mesh)
        else:
            _run_dt_chunk(payloads, chunk, per_point, mesh)
        if len(payloads) == 1:
            # chunk boundary of a single-member (big-grid) sweep: the
            # executing leader IS that member, so its DELETE aborts
            # cleanly between fused programs. With multiple members
            # fused, the batch runs to completion instead — an abort
            # here would fail the leader's NEIGHBORS for the leader's
            # cancellation (the ambient token is the leader's)
            check_cancelled()
    for member in live:
        payload = payloads[member]
        points = []
        for point in range(len(payload["grid"])):
            entry = per_point[(member, point)]
            points.append({**entry, "grid": payload["grid"][point]})
        accuracies = [p["accuracy"] for p in points]
        best = int(np.argmax(accuracies))
        outcomes[member] = (
            "ok",
            {
                "kind": kind,
                "points": [
                    {
                        "grid": p["grid"],
                        "accuracy": p["accuracy"],
                        "weighted_f1": p["weighted_f1"],
                    }
                    for p in points
                ],
                "params": [p["params"] for p in points],
                "best": best,
                "_attribution": {
                    "rows": payload["rows"],
                    "bytes": int(
                        payload["X"].nbytes + payload["X_eval"].nbytes
                    ),
                    "points": len(points),
                },
            },
        )
    return outcomes


def _run_lr_chunk(payloads, chunk, per_point, mesh) -> None:
    first = payloads[chunk[0][0]]
    features = first["X"].shape[1]
    num_classes = first["num_classes"]
    max_iter = first["max_iter"]
    padded, sharding = _job_axis(len(chunk), mesh)
    # dummy slots replicate slot 0's (member, point) pair
    slots = [chunk[i] for i in padded_indices(len(chunk), padded)]
    members = [member for member, _ in slots]
    l2s = np.asarray(
        [payloads[member]["grid"][point]["reg_param"] for member, point in slots],
        np.float32,
    )
    with _tracing.span(
        "coalesce:lr_chunk", slices=len(chunk), padded=padded
    ):
        Xs = _stack([payloads[m]["X"] for m in members], sharding)
        ys = _stack([payloads[m]["y"] for m in members], sharding)
        masks = _stack([payloads[m]["mask"] for m in members], sharding)
        # standardized per slice ON DEVICE by the solo fit's recipe
        # (logistic._lr_standardize, so the paths cannot drift): λ
        # shares one standardization, members each get their own
        Xs, means, scales = _standardize_members(Xs, masks)
        params = jax.device_put(
            {
                "w": jnp.zeros((padded, features, num_classes), jnp.float32),
                "b": jnp.zeros((padded, num_classes), jnp.float32),
            },
            sharding,
        )
        l2_dev = jax.device_put(l2s, sharding)
        states = jax.vmap(_lbfgs_state)(params)
        # watchdog-safe segmentation, like the solo fit, with the job
        # axis multiplying the per-program row cost; NO plateau exit —
        # batched stopping must not couple members (module docstring)
        iters = segment_steps(
            max_iter, first["X"].shape[0] * padded, _LR_ROW_ITERS_BUDGET,
            features,
        )
        for index in range(max(1, max_iter // iters)):
            params, states, _ = _lr_fused_segment(
                params, states, Xs, ys, masks, l2_dev, iters, index == 0
            )
        Xe = _stack([payloads[m]["X_eval"] for m in members], sharding)
        ye = _stack([payloads[m]["y_eval"] for m in members], sharding)
        we = _stack([payloads[m]["mask_eval"] for m in members], sharding)
        accuracy, weighted_f1 = _lr_fused_eval(
            params, Xe, means, scales, ye, we, num_classes
        )
        # ONE host transfer for the whole chunk's params + metrics
        w_host, b_host, mean_host, scale_host, acc_host, f1_host = (
            jax.device_get(
                (params["w"], params["b"], means, scales, accuracy,
                 weighted_f1)
            )
        )
    for i, (member, point) in enumerate(chunk):
        per_point[(member, point)] = {
            "accuracy": float(acc_host[i]),
            "weighted_f1": float(f1_host[i]),
            "params": {
                "kind": "lr",
                "w": np.asarray(w_host[i]),
                "b": np.asarray(b_host[i]),
                "mean": np.asarray(mean_host[i]),
                "scale": np.asarray(scale_host[i]),
            },
        }


def _run_dt_chunk(payloads, chunk, per_point, mesh) -> None:
    first = payloads[chunk[0][0]]
    num_classes = first["num_classes"]
    # depth is a static program shape: group this chunk's slices by
    # depth and run one fused program per distinct depth — each still a
    # batched job axis, never one dispatch per grid point
    by_depth: dict[int, list[tuple[int, int]]] = {}
    for member, point in chunk:
        depth = payloads[member]["grid"][point]["max_depth"]
        by_depth.setdefault(depth, []).append((member, point))
    for depth, group in sorted(by_depth.items()):
        padded, sharding = _job_axis(len(group), mesh)
        members = [
            group[i][0] for i in padded_indices(len(group), padded)
        ]
        with _tracing.span(
            "coalesce:dt_chunk", slices=len(group), padded=padded,
            depth=depth,
        ):
            Xs = _stack([payloads[m]["X"] for m in members], sharding)
            ys = _stack([payloads[m]["y"] for m in members], sharding)
            ws = _stack([payloads[m]["mask"] for m in members], sharding)
            ths = _stack(
                [payloads[m]["thresholds"] for m in members], sharding
            )
            Xe = _stack([payloads[m]["X_eval"] for m in members], sharding)
            ye = _stack([payloads[m]["y_eval"] for m in members], sharding)
            we = _stack([payloads[m]["mask_eval"] for m in members], sharding)
            features_heap, thresholds_heap, leaf_probs, accuracy, f1 = (
                _dt_fused(
                    Xs, ys, ws, ths, Xe, ye, we,
                    num_classes, depth, MAX_BINS,
                )
            )
            fh, th, lp, acc_host, f1_host = jax.device_get(
                (features_heap, thresholds_heap, leaf_probs, accuracy, f1)
            )
        for i, (member, point) in enumerate(group):
            per_point[(member, point)] = {
                "accuracy": float(acc_host[i]),
                "weighted_f1": float(f1_host[i]),
                "params": {
                    "kind": "dt",
                    "features_heap": np.asarray(fh[i]),
                    "thresholds_heap": np.asarray(th[i]),
                    "leaf_probs": np.asarray(lp[i]),
                    "max_depth": depth,
                },
            }


# --------------------------------------------------------------------------
# Model reconstruction + the service-level sweep orchestration
# --------------------------------------------------------------------------

def model_from_params(params: dict, mesh: Optional[Mesh] = None):
    """A predict-ready model from one grid point's fitted params — the
    object the argmax checkpoint serializes."""
    mesh = resolve_mesh(mesh)
    if params["kind"] == "lr":
        from learningorchestra_tpu.ml.logistic import LogisticRegressionModel

        return LogisticRegressionModel(
            {"w": jnp.asarray(params["w"]), "b": jnp.asarray(params["b"])},
            jnp.asarray(params["mean"]),
            jnp.asarray(params["scale"]),
            mesh,
        )
    from learningorchestra_tpu.ml.trees import _TreeEnsembleModel

    return _TreeEnsembleModel(
        jnp.asarray(params["features_heap"])[None],
        jnp.asarray(params["thresholds_heap"])[None],
        jnp.asarray(params["leaf_probs"])[None],
        mesh,
        params["max_depth"],
    )


def run_sweep(
    store: DocumentStore,
    body: dict,
    *,
    jobs,
    coalescer,
    models_dir: Optional[str] = None,
    mesh: Optional[Mesh] = None,
) -> dict:
    """The ``POST /models/sweep`` flow: prep on the request thread, ONE
    coalescible device job for the whole grid (concurrent sweeps with
    compatible shapes fuse), argmax checkpoint published atomically,
    per-point metrics persisted as collection ``sweep_name``.

    Raises whatever the member job raises (the route maps
    ``QueueFullError`` to 429 and ``DuplicateJobError`` to 409)."""
    from learningorchestra_tpu.frame.pyspark_compat import run_preprocessor
    from learningorchestra_tpu.ml.builder import (
        FEATURES_COL,
        LABEL_COL,
        load_dataframe,
    )
    from learningorchestra_tpu.ml.checkpoint import checkpoint_path, save_model
    from learningorchestra_tpu.sched.cancel import CancelToken
    from learningorchestra_tpu.sched.scheduler import DEVICE_CLASS

    mesh = resolve_mesh(mesh)
    name = body["sweep_name"]
    training_df = load_dataframe(store, body["training_filename"])
    testing_df = load_dataframe(store, body["test_filename"])
    out = run_preprocessor(body["preprocessor_code"], training_df, testing_df)
    eval_df = (
        out["features_evaluation"]
        if out["features_evaluation"] is not None
        else out["features_testing"]
    )
    key, payload = prepare_member(
        body["classificator"],
        out["features_training"].feature_matrix(FEATURES_COL),
        out["features_training"].label_vector(LABEL_COL),
        eval_df.feature_matrix(FEATURES_COL),
        eval_df.label_vector(LABEL_COL),
        body["grid"],
        mesh=mesh,
        max_iter=int(body.get("max_iter", _DEFAULT_MAX_ITER)),
    )
    token = CancelToken()
    member = coalescer.register(
        key, payload, group_runner(mesh), token=token, name=f"sweep:{name}"
    )
    try:
        # collection=name opts the member into the journal (ISSUE: each
        # member keeps its own journal entry); store= is deliberately
        # NOT passed — the failure-marking write it enables targets a
        # collection that only exists after success, a guaranteed no-op
        jobs.run_sync(
            f"sweep:{name}",
            coalescer.run_member,
            member,
            job_class=DEVICE_CLASS,
            token=token,
            collection=name,
        )
    except BaseException:
        # a submission that never ran (429 queue cap, 409 duplicate)
        # must not leave a payload for some future leader to stack;
        # harmless no-op when the member already executed and failed
        coalescer.abandon(member)
        raise
    result = member.result
    best = result["best"]
    checkpoint = None
    if models_dir:
        os.makedirs(models_dir, exist_ok=True)
        checkpoint = checkpoint_path(models_dir, name)
        # atomic publication (temp + os.replace, ml/checkpoint.py): the
        # serving registry's rev stamp sees the winner, never a partial
        save_model(model_from_params(result["params"][best], mesh), checkpoint)
        # publish-time serve warmup (compile plane): feature width is
        # derivable from the winner's own params where the model
        # records it (lr/nb); the handler skips kinds that don't
        from learningorchestra_tpu import compile as lo_compile

        lo_compile.checkpoint_published(checkpoint)
    points = [
        {**p["grid"], "accuracy": p["accuracy"], "weighted_f1": p["weighted_f1"]}
        for p in result["points"]
    ]
    document = {
        ROW_ID: 0,
        "filename": name,
        "classificator": result["kind"],
        "points": points,
        "best": best,
        "model_checkpoint": checkpoint,
        "finished": True,
    }
    store.insert_one(name, document)
    return {
        "model": name,
        "classificator": result["kind"],
        "points": points,
        "best": best,
        "model_checkpoint": checkpoint,
    }
