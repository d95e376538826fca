"""t-SNE on device: exact, data-parallel over the mesh, with a landmark
path for datasets past the O(n²) wall.

Replaces the reference's driver-side ``sklearn.manifold.TSNE()
.fit_transform`` (reference: microservices/tsne_image/tsne.py:87-88) —
single-host, O(n²), the headline scalability cliff (SURVEY.md §3.4,
BASELINE.json north-star metric).

TPU shape — every stage is matmul/elementwise:

- pairwise squared distances via ``‖x‖² + ‖y‖² − 2 X Xᵀ`` (MXU);
- per-row bandwidth calibration to the target perplexity as a
  vectorized 32-step bisection (no data-dependent Python control flow);
- the gradient ``4 (diag(W·1) − W) Y`` as two matmuls per iteration
  inside ``lax.fori_loop`` with momentum + adaptive gains, early
  exaggeration folded in by phase.

Parallelism: both the affinity build and the gradient loop run under
``jax.shard_map`` with rows split over the mesh's ``data`` axis — each
chip owns an ``(n/D, n)`` slab of P and of the repulsion matrix, the
single global scalar (the Q normalizer) is a ``psum`` over ICI, and the
``(n, 2)`` gradient is an ``all_gather`` (tiny) so the embedding state
stays replicated. Rows are zero-padded to the mesh size with a validity
mask; padded rows have zero affinity and zero repulsion weight, so they
never influence real points. Per-chip memory is O(n²/D), the exact
algorithm's floor.

Past ``EXACT_ROWS_LIMIT`` rows the ``landmark`` method runs exact t-SNE
on a random subsample and places every remaining row by
perplexity-calibrated kernel regression onto the landmark embedding —
an ``(n, m)`` matmul pipeline that is row-sharded and chunked, so 1M+
rows fit comfortably on one chip and scale linearly with the data axis.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as PSpec

from learningorchestra_tpu.ml.base import resolve_mesh
from learningorchestra_tpu.parallel.mesh import DATA_AXIS, data_size
from learningorchestra_tpu.parallel.multihost import fetch

PERPLEXITY = 30.0
ITERATIONS = 1000
EARLY_EXAGGERATION = 12.0
EARLY_PHASE = 250
LEARNING_RATE = 200.0
CHUNK = 1024
# Exact t-SNE holds O(n²/D) per chip; past this the landmark path wins.
EXACT_ROWS_LIMIT = 20_000
LANDMARKS = 5_000
INTERP_CHUNK = 8_192
# Rows per _interpolate dispatch: keeps one interpolation program short
# at any n (see ml/base.segment_steps).
_INTERP_ROWS_PER_PROGRAM = 4_000_000


def _squared_distances(A, B):
    # precision=HIGHEST: the TPU's default bf16 matmul makes
    # ‖a‖²+‖b‖²−2ab come out slightly NEGATIVE for near neighbors once
    # coordinates grow; 1/(1+d) then blows past zero and the whole
    # optimization NaNs. Full-f32 passes on the MXU cost ~3× on this one
    # contraction and keep the identity non-negative to rounding.
    return jnp.maximum(
        jnp.sum(A**2, axis=1)[:, None]
        + jnp.sum(B**2, axis=1)[None, :]
        - 2.0 * jnp.dot(A, B.T, precision=jax.lax.Precision.HIGHEST),
        0.0,
    )


def _calibrate_row_block(block_distances, excluded, perplexity):
    """Per-row Gaussian bandwidths matching ``log(perplexity)`` entropy,
    by bisection on beta = 1/(2σ²). Fully vectorized over the block.
    ``excluded`` masks columns that must get zero affinity (each row's
    own column, padding) — self-affinity is excluded by INDEX, so
    duplicate rows keep their (maximal) mutual affinity like sklearn's
    TSNE."""
    target = jnp.log(perplexity)

    def entropy_and_p(beta):
        # numerically stable: distances are shifted per-row
        logits = -block_distances * beta[:, None]
        logits = logits - logits.max(axis=1, keepdims=True)
        p = jnp.exp(logits)
        p = p * ~excluded
        total = jnp.maximum(p.sum(axis=1, keepdims=True), 1e-12)
        p = p / total
        entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=1)
        return entropy, p

    def bisect(state, _):
        low, high, beta = state
        entropy, _ = entropy_and_p(beta)
        too_high = entropy > target  # entropy too high → increase beta
        low = jnp.where(too_high, beta, low)
        high = jnp.where(too_high, high, beta)
        beta = jnp.where(
            jnp.isinf(high), beta * 2.0, (low + high) / 2.0
        )
        return (low, high, beta), None

    m = block_distances.shape[0]
    init = (
        jnp.zeros(m),
        jnp.full(m, jnp.inf),
        jnp.ones(m),
    )
    (_, _, beta), _ = jax.lax.scan(bisect, init, length=32)
    _, p = entropy_and_p(beta)
    return p


@partial(jax.jit, static_argnames=("mesh", "chunk"))
def _affinities(mesh: Mesh, X, valid, perplexity, chunk: int):
    """Symmetrized conditional affinities P, row-sharded over ``data``.

    ``X``/``valid`` are replicated ``(n_pad, …)``; each chip builds its
    own ``(n_pad/D, n_pad)`` slab, chunked block-of-rows at a time so
    the distance transient is ``(chunk, n_pad)``, not the full square.
    Padded rows/columns get exactly zero affinity.
    """
    n_pad = X.shape[0]
    shards = data_size(mesh)
    local = n_pad // shards
    pad_local = -(-local // chunk) * chunk

    def local_slab(X_full, valid_full):
        row0 = jax.lax.axis_index(DATA_AXIS) * local
        X_local = jax.lax.dynamic_slice_in_dim(X_full, row0, local, 0)
        X_local = jnp.pad(X_local, ((0, pad_local - local), (0, 0)))
        blocks = X_local.reshape(-1, chunk, X_full.shape[1])
        offsets = row0 + jnp.arange(blocks.shape[0]) * chunk

        def one_block(args):
            block, offset = args
            distances = _squared_distances(block, X_full)
            rows = offset + jnp.arange(chunk)
            excluded = (rows[:, None] == jnp.arange(n_pad)[None, :]) | (
                ~valid_full[None, :]
            )
            p = _calibrate_row_block(distances, excluded, perplexity)
            # zero out padded rows (clamped indexing is fine: overhang
            # rows are sliced off below)
            return p * valid_full[jnp.minimum(rows, n_pad - 1), None]

        slab = jax.lax.map(one_block, (blocks, offsets))
        return slab.reshape(pad_local, n_pad)[:local]

    P = jax.shard_map(
        local_slab,
        mesh=mesh,
        in_specs=(PSpec(), PSpec()),
        out_specs=PSpec(DATA_AXIS),
        check_vma=False,
    )(X, valid)
    n_valid = valid.sum().astype(P.dtype)
    P = (P + P.T) / (2.0 * n_valid)
    return jnp.maximum(P, 1e-12)


@partial(jax.jit, static_argnames=("mesh", "iterations", "early_phase"))
def _optimize(
    mesh: Mesh, P, Y0, valid, iterations: int, early_phase: int,
    learning_rate, exaggeration,
):
    """Gradient descent with momentum + adaptive gains, sharded like P:
    each chip computes its row slab of the attraction/repulsion matrix,
    the Q normalizer is one psum, and the (n, 2) gradient is
    all_gathered so Y/velocity/gains stay replicated (tiny state)."""
    n_pad = Y0.shape[0]
    shards = data_size(mesh)
    local = n_pad // shards

    def run(P_local, Y0_full, valid_full):
        row0 = jax.lax.axis_index(DATA_AXIS) * local
        valid_local = jax.lax.dynamic_slice_in_dim(valid_full, row0, local, 0)
        rows = row0 + jnp.arange(local)
        pair_mask = (
            valid_local[:, None]
            & valid_full[None, :]
            & (rows[:, None] != jnp.arange(n_pad)[None, :])
        )

        def gradient(Y, P_eff):
            Y_local = jax.lax.dynamic_slice_in_dim(Y, row0, local, 0)
            distances = _squared_distances(Y_local, Y)
            inv = (1.0 / (1.0 + distances)) * pair_mask
            total = jax.lax.psum(inv.sum(), DATA_AXIS)
            Q = inv / jnp.maximum(total, 1e-12)
            W = (P_eff - jnp.maximum(Q, 1e-12)) * inv
            grad_local = 4.0 * (
                W.sum(axis=1)[:, None] * Y_local
                - jnp.dot(W, Y, precision=jax.lax.Precision.HIGHEST)
            )
            return jax.lax.all_gather(
                grad_local, DATA_AXIS, axis=0, tiled=True
            )

        def step(i, state):
            Y, velocity, gains = state
            P_eff = jnp.where(i < early_phase, P_local * exaggeration, P_local)
            grad = gradient(Y, P_eff).astype(Y.dtype)
            momentum = jnp.where(i < early_phase, 0.5, 0.8).astype(Y.dtype)
            same_sign = jnp.sign(grad) == jnp.sign(velocity)
            gains = jnp.maximum(
                jnp.where(same_sign, gains * 0.8, gains + 0.2), 0.01
            )
            velocity = momentum * velocity - learning_rate * gains * grad
            return Y + velocity, velocity, gains

        Y, _, _ = jax.lax.fori_loop(
            0,
            iterations,
            step,
            (Y0_full, jnp.zeros_like(Y0_full), jnp.ones_like(Y0_full)),
        )
        return Y

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(PSpec(DATA_AXIS), PSpec(), PSpec()),
        out_specs=PSpec(),
        check_vma=False,
    )(P, Y0, valid)


def _pad_for_mesh(X: np.ndarray, mesh: Mesh, chunk: int) -> tuple:
    """Zero-pad rows to the bucketed shape grid (sharding.bucket_rows —
    nearby sizes reuse one compiled affinity/optimize program), build
    the validity mask, and pick the per-chip chunk size."""
    from learningorchestra_tpu.parallel.sharding import padded_row_count

    shards = data_size(mesh)
    n = len(X)
    n_pad = padded_row_count(n, shards)
    valid = np.zeros(n_pad, dtype=bool)
    valid[:n] = True
    X_pad = np.pad(X, ((0, n_pad - n), (0, 0)))
    chunk = max(1, min(chunk, n_pad // shards))
    return X_pad, valid, chunk


def _tsne_exact(
    X: np.ndarray,
    mesh: Mesh,
    perplexity: float,
    iterations: int,
    learning_rate: float,
    seed: int,
) -> np.ndarray:
    n = len(X)
    X_pad, valid, chunk = _pad_for_mesh(X, mesh, CHUNK)
    replicated = NamedSharding(mesh, PSpec())
    X_dev = jax.device_put(jnp.asarray(X_pad), replicated)
    valid_dev = jax.device_put(jnp.asarray(valid), replicated)
    return _tsne_exact_on_device(
        X_dev, valid_dev, n, mesh, perplexity, iterations, learning_rate,
        seed, chunk,
    )


def _tsne_exact_on_device(
    X_dev,
    valid_dev,
    n: int,
    mesh: Mesh,
    perplexity: float,
    iterations: int,
    learning_rate: float,
    seed: int,
    chunk: int,
) -> np.ndarray:
    """Exact t-SNE over already-replicated device buffers — the shared
    tail of the host-array path and the cached-DeviceMatrix path (which
    reshards the cached row-sharded buffers on device instead of
    re-crossing the PCIe boundary)."""
    perplexity = min(perplexity, max((n - 1) / 3.0, 1.0))
    replicated = NamedSharding(mesh, PSpec())
    P = _affinities(mesh, X_dev, valid_dev, jnp.float32(perplexity), chunk)
    Y0 = (
        jax.random.normal(
            jax.random.key(seed), (X_dev.shape[0], 2), jnp.float32
        )
        * 1e-4
    )
    Y0 = jax.device_put(Y0, replicated)
    Y = _optimize(
        mesh,
        P,
        Y0,
        valid_dev,
        iterations,
        min(EARLY_PHASE, iterations // 2),
        jnp.float32(learning_rate),
        jnp.float32(EARLY_EXAGGERATION),
    )
    from learningorchestra_tpu.telemetry import profile, span

    with span("d2h:tsne", rows=n):
        out = fetch(Y)[:n]
        profile.account_d2h(int(np.asarray(out).nbytes))
        return out


@partial(jax.jit, static_argnames=("mesh", "chunk"))
def _interpolate(mesh: Mesh, X, landmarks, Y_landmarks, perplexity, chunk: int):
    """Out-of-sample placement: perplexity-calibrated Gaussian affinities
    from each row to the landmark set, then one ``P @ Y_L`` matmul. Rows
    are sharded over ``data`` and processed in chunks, so the transient
    is ``(chunk, m)`` per chip — linear scaling in n."""
    n_pad = X.shape[0]
    local = n_pad // data_size(mesh)

    def run(X_local, L_full, Y_full):
        blocks = X_local.reshape(-1, chunk, X_local.shape[1])

        def one_block(block):
            distances = _squared_distances(block, L_full)
            excluded = jnp.zeros(distances.shape, bool)
            p = _calibrate_row_block(distances, excluded, perplexity)
            return jnp.dot(p, Y_full, precision=jax.lax.Precision.HIGHEST)

        return jax.lax.map(one_block, blocks).reshape(local, 2)

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(PSpec(DATA_AXIS), PSpec(), PSpec()),
        out_specs=PSpec(DATA_AXIS),
        check_vma=False,
    )(X, landmarks, Y_landmarks)


def _tsne_landmark(
    X: np.ndarray,
    mesh: Mesh,
    perplexity: float,
    iterations: int,
    learning_rate: float,
    seed: int,
    landmarks: int,
) -> np.ndarray:
    from learningorchestra_tpu.telemetry import span

    n = len(X)
    rng = np.random.default_rng(seed)
    m = min(landmarks, n)
    chosen = rng.choice(n, size=m, replace=False)
    L = X[chosen]
    # Phase spans: the landmark path is (exact fit on m rows) +
    # (interpolate n rows); each phase ends in a blocking fetch, so
    # these wall-clocks are honest — they are the attribution that
    # localizes a landmark-path regression to the phase that moved.
    with span("tsne:landmark_fit", rows=m):
        Y_L = _tsne_exact(L, mesh, perplexity, iterations, learning_rate, seed)
    if m == n:
        # Every row IS a landmark: the exact embedding is already the
        # answer — undo the sampling permutation instead of blurring it
        # through interpolation.
        out = np.empty((n, 2), np.float32)
        out[chosen] = Y_L
        return out

    shards = data_size(mesh)
    chunk = min(INTERP_CHUNK, -(-n // shards))
    multiple = shards * chunk
    replicated = NamedSharding(mesh, PSpec())
    row_sharded = NamedSharding(mesh, PSpec(DATA_AXIS))
    L_dev = jax.device_put(jnp.asarray(L), replicated)
    Y_L_dev = jax.device_put(jnp.asarray(Y_L, np.float32), replicated)
    interp_perplexity = min(perplexity, max((m - 1) / 3.0, 1.0))

    # Macro-batch the interpolation: one _interpolate call is ONE XLA
    # program sequentially mapping its blocks, and at 100M rows that is
    # a ~20-minute single execution that nothing can cancel or bound
    # (same reasoning as ml/base.segment_steps). Below the per-program
    # row budget the
    # macro shape follows the BUCKETED dataset size (a 100k dataset
    # must not ride a 4M-row padded program — that 40x compute waste
    # was round 4's 1.1s -> 21.5s landmark regression at 100k); above
    # it, fixed-size slices keep every program short and identically
    # shaped (one compile), the tail slice padded and cropped.
    from learningorchestra_tpu.parallel.sharding import padded_row_count

    if n <= _INTERP_ROWS_PER_PROGRAM:
        macro = padded_row_count(n, multiple)
    else:
        macro = max(
            multiple, (_INTERP_ROWS_PER_PROGRAM // multiple) * multiple
        )
    with span(
        "tsne:interpolate", rows=n, landmarks=m, macro_rows=macro
    ):
        outs = []
        for start in range(0, n, macro):
            stop = min(start + macro, n)
            block = X[start:stop]
            padded = np.pad(block, ((0, macro - len(block)), (0, 0)))
            X_dev = jax.device_put(jnp.asarray(padded), row_sharded)
            Y = _interpolate(
                mesh, X_dev, L_dev, Y_L_dev, jnp.float32(interp_perplexity),
                chunk,
            )
            outs.append(np.asarray(fetch(Y))[: len(block)])
    return np.concatenate(outs) if len(outs) > 1 else outs[0]


def tsne_embedding(
    X,
    perplexity: float = PERPLEXITY,
    iterations: int = ITERATIONS,
    learning_rate: float = LEARNING_RATE,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    method: str = "auto",
    exact_rows_limit: int = EXACT_ROWS_LIMIT,
    landmarks: int = LANDMARKS,
) -> np.ndarray:
    """2-D t-SNE embedding of ``X``. Returns ``(rows, 2)``.

    ``method``: ``"exact"`` (O(n²/chip), sharded over the data axis),
    ``"landmark"`` (exact on a subsample + calibrated kernel regression
    for the rest — linear in n), or ``"auto"`` (exact up to
    ``exact_rows_limit`` rows).

    ``X`` may be an already-sharded :class:`~learningorchestra_tpu.ml.
    base.DeviceMatrix` (the device cache's currency, core/devcache.py).
    The exact path reshards the cached buffers on device — the dataset
    never re-crosses the PCIe boundary and only the ``(rows, 2)``
    embedding comes back. The landmark path needs host rows for
    subsampling and macro-batching, so a cached matrix pays one D2H
    there — still strictly cheaper than re-reading the store over the
    wire. (Same padded-shape rule both ways: ``shard_rows`` and
    ``_pad_for_mesh`` share ``padded_row_count``.)
    """
    from learningorchestra_tpu.ml.base import DeviceMatrix

    mesh = resolve_mesh(mesh)
    if isinstance(X, DeviceMatrix):
        n = len(X)
        if method == "auto":
            method = "exact" if n <= exact_rows_limit else "landmark"
        if (
            method == "exact"
            and X.mesh is mesh
            and jax.process_count() == 1
        ):
            shards = data_size(mesh)
            chunk = max(1, min(CHUNK, X.data.shape[0] // shards))
            replicated_sharding = NamedSharding(mesh, PSpec())
            return _tsne_exact_on_device(
                jax.device_put(X.data.astype(jnp.float32), replicated_sharding),
                jax.device_put(X.mask, replicated_sharding),
                n,
                mesh,
                perplexity,
                iterations,
                learning_rate,
                seed,
                chunk,
            )
        # landmark (or mesh/process mismatch): one D2H of the cached
        # buffer replaces the wire read (fetch gathers across hosts —
        # every process enters tsne_embedding, so the collective lines
        # up)
        X = np.asarray(fetch(X.data))[:n]
    X = np.asarray(X, np.float32)
    if method == "auto":
        method = "exact" if len(X) <= exact_rows_limit else "landmark"
    if method == "exact":
        return _tsne_exact(X, mesh, perplexity, iterations, learning_rate, seed)
    if method == "landmark":
        return _tsne_landmark(
            X, mesh, perplexity, iterations, learning_rate, seed, landmarks
        )
    raise ValueError(f"unknown t-SNE method {method!r}")
