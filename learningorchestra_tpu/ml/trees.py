"""Histogram-binned decision trees, random forest, and gradient boosting.

Replaces Spark MLlib's ``DecisionTreeClassifier`` / ``RandomForestClassifier``
/ ``GBTClassifier`` (reference: microservices/model_builder_image/
model_builder.py:8-12,153-155). Defaults mirror MLlib: ``maxDepth=5``,
``maxBins=32``; RF ``numTrees=20`` with sqrt feature subsets per node;
GBT ``maxIter=20``, ``stepSize=0.1``, binary logistic loss.

TPU-first design — no recursive node objects, no data-dependent control
flow:

- Features are quantile-binned once (``ml/binning.py``); a tree level is
  then ONE dense program: accumulate per-row stat vectors into a
  ``(node, feature, bin, channel)`` histogram (one wide bfloat16-exact
  contraction per row tile, :func:`_level_histograms`), cumulative-sum
  over bins, and an argmax — the classic LightGBM/XGBoost histogram
  method, which is exactly the shape of computation XLA tiles well.
- The tree is a static heap (arrays of size ``2^depth - 1``); rows carry
  an int32 node index and each level doubles it. Nodes that stop
  splitting get ``feature = -1`` and route everything left, so shapes
  never change.
- One generic ``channel`` dimension serves both worlds: class one-hots
  (gini splits, used by dt/rf) and Newton ``(g, h)`` pairs (logistic
  boosting, used by gb).
- Random forest is ``vmap`` over per-tree RNG keys — all 20 trees grow
  simultaneously on device, with Poisson(1) bootstrap weights and
  per-node feature subsets. Boosting is ``lax.scan`` over rounds.
- Row-sharded inputs: the histograms are accumulated shard-locally and
  reduce over the ``data`` mesh axis once a feature block; XLA inserts
  the cross-chip all-reduce from the sharding annotations.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.ml import progress as _progress
from learningorchestra_tpu.ml.base import (
    FittedModel,
    infer_num_classes,
    largest_divisor,
    prepare_xy,
    resolve_mesh,
)
from learningorchestra_tpu.ml.binning import (
    MAX_BINS,
    apply_bins,
    distinct_thresholds,
    shared_thresholds,
)
from learningorchestra_tpu.parallel.mesh import MODEL_AXIS, model_size
from learningorchestra_tpu.telemetry import tracing as _tracing

MAX_DEPTH = 5          # MLlib default maxDepth
NUM_TREES = 20         # MLlib default numTrees (RF)
GBT_ROUNDS = 20        # MLlib default maxIter (GBT)
GBT_STEP = 0.1         # MLlib default stepSize
EPS = 1e-12
# How the level histograms walk a table (hist_block_plan): the rows a
# step of the tile loop contracts, summed over the row groups; the
# contiguous row groups that accumulate side by side (a batch dimension
# of the contraction: on a mesh it keeps the loop shard-local, and on
# one chip the TPU compiler takes minutes over the unbatched form); and
# the bytes the one bfloat16 bin indicator alive at a time may take,
# which caps the columns of a feature block. PERF.md §6 (PR 35) has the
# chip readings that chose them.
_HIST_TILE_ROWS = 16384
_HIST_ROW_GROUPS = 8
_HIST_INDICATOR_BYTES = 2**27


def hist_block_plan(rows: int, num_features: int, max_bins: int) -> dict:
    """The geometry of a level's histogram accumulate, from the static
    shapes alone (host arithmetic, no device work): the rows of one
    tile (``hist_tile_rows``: every row group's share of a step, so a
    tile is small whatever the table's height), the columns of a
    feature block and the blocks a level, and the bytes of the one
    bfloat16 bin indicator alive at a time, ``tile x block x bins x 2``.
    The fits stamp it on ``fit:enqueue`` and :func:`_level_histograms`
    takes its tiles and blocks from here, so the two cannot drift
    apart. A table whose rows the tiles do not divide is padded up to
    them with weightless rows; the bucketed row counts of
    ``parallel/sharding.py`` divide evenly."""
    steps = max(1, -(-rows // _HIST_TILE_ROWS))
    tile = -(-max(rows, 1) // (_HIST_ROW_GROUPS * steps)) * _HIST_ROW_GROUPS
    cap = max(1, int(_HIST_INDICATOR_BYTES // (tile * max_bins * 2)))
    block = largest_divisor(num_features, cap)
    return {
        "hist_tile_rows": tile,
        "hist_block_features": block,
        "hist_blocks": num_features // block,
        "hist_indicator_bytes": tile * block * max_bins * 2,
    }


# --------------------------------------------------------------------------
# Level primitives
# --------------------------------------------------------------------------

def _split_bf16(x):
    """``x`` (float32) as three bfloat16 pieces whose float32 sum is
    ``x`` again: each piece takes the next eight significand bits, so a
    one-pass bfloat16 matmul against an exact 0/1 operand, accumulated
    in float32, gives what a float32 matmul gives.

    A piece is cut by clearing the low sixteen bits of the float32
    pattern, not by a round trip through bfloat16: inside a fused
    program the TPU keeps a bfloat16 intermediate in float32 registers,
    so ``x - x.astype(bfloat16)`` reads zero there and the two lower
    pieces with it (PERF.md §6, PR 35: the chip's gradient sums then
    carried eight bits). The masked values are bfloat16 values exactly,
    so the casts at the end round nothing. A piece under the smallest
    normal float32 (1.2e-38: a subnormal value, or the low piece of a
    value under 2e-31) may be flushed to zero, as the hardware flushes
    any such float32."""

    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32
        )

    hi = top(x)
    rest = x - hi
    mid = top(rest)
    lo = rest - mid
    return tuple(piece.astype(jnp.bfloat16) for piece in (hi, mid, lo))


def _level_histograms(bins, node, channels, n_nodes: int, max_bins: int):
    """Accumulate per-row channel vectors into ``(node, feature, bin, K)``.

    The histogram-build hot loop: O(rows x features) accumulation, the
    tree analogue of the reference's distributed MLlib fit iterations
    (model_builder.py:199).

    MXU formulation: the scatter-add is algebraically
    ``one_hot(bin).T @ (one_hot(node) ⊗ channels)``, which the systolic
    array executes where a batched scatter (under the forest's
    tree-vmap) serializes. It runs as ONE wide contraction per row
    tile (:func:`hist_block_plan`): the rows are walked in tiles, each
    tile's bin indicator is built in bfloat16 for a whole block of
    columns (0 and 1 are exact, and a tile is small whatever the
    table's height, so a block holds many columns), contracted over the
    tile's rows against the channel operand, and added into a float32
    accumulator. The channel operand, the only side with float32
    values, goes in as three bfloat16 pieces side by side
    (:func:`_split_bf16`), so every product is exact and every sum is
    float32: counts are exact whatever the order of the tiles, gradient
    sums differ from a plain float32 sum by reassociation only.

    The rows are cut into ``_HIST_ROW_GROUPS`` contiguous groups that
    accumulate side by side and are summed once the tiles are walked.
    On a mesh whose ``data`` axis divides the groups each group lies
    inside one row shard: the tile loop runs shard-local and that one
    sum is the only reduction over ``data``, once a feature block (a
    loop over the sharded row axis itself would gather the matrix).

    The scatter fallback guards the wide case (many classes at deep
    levels) where the ``(rows, nodes·K)`` operand would not fit.
    """
    num_channels = channels.shape[1]
    num_features = bins.shape[1]
    rows = bins.shape[0]
    width = n_nodes * num_channels

    if width <= 64:
        plan = hist_block_plan(rows, num_features, max_bins)
        block, blocks = plan["hist_block_features"], plan["hist_blocks"]
        groups = _HIST_ROW_GROUPS
        tile = plan["hist_tile_rows"] // groups
        steps = -(-rows // plan["hist_tile_rows"])
        pad = steps * plan["hist_tile_rows"] - rows
        if pad:  # weightless rows: zero channels add nothing
            bins = jnp.pad(bins, ((0, pad), (0, 0)))
            node = jnp.pad(node, (0, pad))
            channels = jnp.pad(channels, ((0, pad), (0, 0)))

        def by_step(a):
            # (step, group, tile row, ...): a step takes one tile of
            # every group
            return jnp.swapaxes(
                a.reshape(groups, steps, tile, *a.shape[1:]), 0, 1
            )

        node_t, channels_t = by_step(node), by_step(channels)
        # (block, step, group, tile row, column of the block)
        bins_t = jnp.moveaxis(
            by_step(bins).reshape(steps, groups, tile, blocks, block), 3, 0
        )
        bin_ids = jnp.arange(max_bins, dtype=jnp.int32)
        node_ids = jnp.arange(n_nodes, dtype=jnp.int32)

        def add_tile(acc, tile_of):
            bins_b, node_b, channels_b = tile_of
            # exact 0/1, never a float32 array
            indicator = (bins_b[..., None] == bin_ids).astype(jnp.bfloat16)
            pieces = jnp.stack(_split_bf16(channels_b), axis=-1)  # (g,t,K,3)
            fused = jnp.where(
                (node_b[..., None] == node_ids)[..., None, None],
                pieces[:, :, None],
                0,
            ).reshape(groups, tile, width * 3)
            # one bfloat16 pass (whatever the process-wide default
            # precision says), float32 sums: every product is exact
            return acc + jax.lax.dot_general(
                indicator,
                fused,
                (((1,), (1,)), ((0,), (0,))),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32,
            ), None                      # (g, block, bins, nodes*K*3)

        def per_block(bins_block):
            acc, _ = jax.lax.scan(
                add_tile,
                jnp.zeros((groups, block, max_bins, width * 3), jnp.float32),
                (bins_block, node_t, channels_t),
            )
            hi, mid, lo = jnp.moveaxis(
                acc.sum(axis=0).reshape(block, max_bins, width, 3), 3, 0
            )
            return hi + (mid + lo)               # (block, bins, nodes*K)

        hist = jax.lax.map(per_block, bins_t)    # (F/blk, blk, B, n*K)
        return hist.reshape(
            num_features, max_bins, n_nodes, num_channels
        ).transpose(2, 0, 1, 3)

    def per_feature(bins_f):
        index = node * max_bins + bins_f
        return (
            jnp.zeros((n_nodes * max_bins, num_channels), jnp.float32)
            .at[index]
            .add(channels)
        )

    # Sequential over features (lax.map), parallel over rows within each
    # scatter; keeps the transient at (rows, K) per step.
    hist = jax.lax.map(per_feature, bins.T)              # (F, nodes*B, K)
    return hist.reshape(num_features, n_nodes, max_bins, num_channels).transpose(
        1, 0, 2, 3
    )


def _leaf_sums(leaf_of_row, channels, n_leaves: int):
    """Per-leaf channel sums — the same MXU-vs-scatter choice as
    _level_histograms: one-hot matmul while the ``(rows, n_leaves)``
    intermediate stays small (default depth 5 → 32 leaves), guarded
    scatter for deep trees where it would not fit."""
    if n_leaves <= 64:
        return jnp.dot(
            jax.nn.one_hot(leaf_of_row, n_leaves, dtype=jnp.float32).T,
            channels,
            precision=jax.lax.Precision.HIGHEST,
        )
    return (
        jnp.zeros((n_leaves, channels.shape[1]), jnp.float32)
        .at[leaf_of_row]
        .add(channels)
    )


def _gini_gain(hist):
    """Split scores from class-count histograms ``(nodes, F, B, C)``.

    Maximizing ``Σ_c l_c²/n_l + Σ_c r_c²/n_r`` is minimizing weighted
    gini impurity; the parent term makes it a proper gain (> 0 required
    to split, MLlib ``minInfoGain=0``)."""
    left = jnp.cumsum(hist, axis=2)
    total = left[:, :, -1:, :]
    right = total - left
    n_left = left.sum(-1)
    n_right = right.sum(-1)
    score_left = (left**2).sum(-1) / jnp.maximum(n_left, EPS)
    score_right = (right**2).sum(-1) / jnp.maximum(n_right, EPS)
    parent = (total[:, :, 0, :] ** 2).sum(-1) / jnp.maximum(
        total[:, :, 0, :].sum(-1), EPS
    )
    gain = score_left + score_right - parent[:, :, None]
    valid = (n_left > 0) & (n_right > 0)
    return jnp.where(valid, gain, -jnp.inf)


def _newton_gain(hist, lam=1.0):
    """Split scores from ``(g, h)`` histograms ``(nodes, F, B, 2)`` —
    XGBoost-style second-order gain for logistic boosting."""
    left = jnp.cumsum(hist, axis=2)
    total = left[:, :, -1:, :]
    right = total - left
    g_left, h_left = left[..., 0], left[..., 1]
    g_right, h_right = right[..., 0], right[..., 1]
    score = g_left**2 / (h_left + lam) + g_right**2 / (h_right + lam)
    parent = total[:, :, 0, 0] ** 2 / (total[:, :, 0, 1] + lam)
    gain = score - parent[:, :, None]
    valid = (h_left > EPS) & (h_right > EPS)
    return jnp.where(valid, gain, -jnp.inf)


def _select_splits(gain, subset_key, subset_k: Optional[int]):
    """Best (feature, bin) per node from ``gain (nodes, F, B)``; nodes
    whose best gain is <= 0 get ``feature = -1`` (leaf). ``subset_k``
    restricts each node to a random feature subset (RF per-node
    sampling, MLlib featureSubsetStrategy="auto" → sqrt)."""
    n_nodes, num_features, max_bins = gain.shape
    if subset_k is not None and subset_k < num_features:
        scores = jax.random.uniform(subset_key, (n_nodes, num_features))
        kth = jnp.sort(scores, axis=1)[:, subset_k - 1]
        allowed = scores <= kth[:, None]
        gain = jnp.where(allowed[:, :, None], gain, -jnp.inf)
    flat = gain.reshape(n_nodes, -1)
    best = jnp.argmax(flat, axis=1).astype(jnp.int32)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    feature = best // max_bins
    bin_index = best % max_bins
    is_leaf = ~(best_gain > 0) | jnp.isinf(best_gain)
    feature = jnp.where(is_leaf, -1, feature)
    return feature, bin_index


def _indicator_lookup(indices, table, fill=0):
    """Gather-free ``table[indices]`` for small tables: an indicator
    select-sum on the VPU. Per-row gathers serialize on TPU and were
    the forest fit's dominant cost — an isolated 20-tree × 5-level
    routing probe on v5e at 1M×16 cost 1.9 s, the same order as the
    entire 1.66 s forest fit, vs 0.16 s for its histograms. A select
    (never a multiply) so 0·inf/0·NaN cannot poison the sum; exactly
    one indicator per row is set, so the sum is exact. Wide tables
    fall back to the native gather — the (rows, size) indicator would
    dwarf the gather it replaces (same ≤64 guard pattern as
    _level_histograms/_leaf_sums)."""
    size = table.shape[0]
    if size > 64:
        return table[indices]
    picked = indices[:, None] == jnp.arange(size, dtype=jnp.int32)
    return jnp.where(picked, table[None, :], fill).sum(axis=1)


def _route(bins, node, feature, bin_index):
    """Advance each row one level down: left iff its bin <= the node's
    split bin; ``feature = -1`` nodes send everything left. All
    per-row lookups are gather-free (see _indicator_lookup)."""
    row_feature = _indicator_lookup(node, feature)
    row_bin = _indicator_lookup(node, bin_index)
    feature_oh = jax.nn.one_hot(
        jnp.maximum(row_feature, 0), bins.shape[1], dtype=bins.dtype
    )
    x_bin = (bins * feature_oh).sum(axis=1)
    go_right = (x_bin > row_bin) & (row_feature >= 0)
    return node * 2 + go_right.astype(jnp.int32)


# --------------------------------------------------------------------------
# Single-tree fits (jit-composable; shapes static over levels)
# --------------------------------------------------------------------------

def _grow(bins, channels, gain_fn, max_depth, max_bins, subset_key, subset_k):
    """Grow one tree level-wise. Returns heap arrays (features, bins per
    internal node) and the per-row final leaf index."""
    n_rows = bins.shape[0]
    node = jnp.zeros(n_rows, jnp.int32)
    features_heap = []
    bins_heap = []
    # the scopes name each stage's XLA ops (lo.leaf: the callers' leaf
    # sums), so a profiler capture says which stage an op belongs to
    for level in range(max_depth):
        with jax.named_scope("lo.hist"):
            hist = _level_histograms(bins, node, channels, 2**level, max_bins)
        with jax.named_scope("lo.split"):
            gain = gain_fn(hist)
            level_key = (
                jax.random.fold_in(subset_key, level)
                if subset_key is not None
                else None
            )
            feature, bin_index = _select_splits(gain, level_key, subset_k)
        features_heap.append(feature)
        bins_heap.append(bin_index)
        with jax.named_scope("lo.route"):
            node = _route(bins, node, feature, bin_index)
    return (
        jnp.concatenate(features_heap),
        jnp.concatenate(bins_heap),
        node,
    )


def _fit_classification_tree(
    bins, one_hot, max_depth, max_bins, subset_key=None, subset_k=None
):
    features_heap, bins_heap, leaf_of_row = _grow(
        bins, one_hot, _gini_gain, max_depth, max_bins, subset_key, subset_k
    )
    num_classes = one_hot.shape[1]
    with jax.named_scope("lo.leaf"):
        leaf_counts = _leaf_sums(leaf_of_row, one_hot, 2**max_depth)
    leaf_probs = leaf_counts / jnp.maximum(leaf_counts.sum(1, keepdims=True), EPS)
    return features_heap, bins_heap, leaf_probs


def _fit_newton_tree(bins, g, h, max_depth, max_bins, lam=1.0):
    channels = jnp.stack([g, h], axis=1)
    features_heap, bins_heap, leaf_of_row = _grow(
        bins, channels, _newton_gain, max_depth, max_bins, None, None
    )
    with jax.named_scope("lo.leaf"):
        sums = _leaf_sums(leaf_of_row, channels, 2**max_depth)
    leaf_values = -sums[:, 0] / (sums[:, 1] + lam)
    return features_heap, bins_heap, leaf_values, leaf_of_row


# --------------------------------------------------------------------------
# Prediction on raw (unbinned) features
# --------------------------------------------------------------------------

def _descend(X, features_heap, thresholds_heap, max_depth):
    """Walk the static heap: raw value <= float threshold goes left —
    identical routing to the binned training walk by construction
    (ml/binning.py bin semantics). ``~(x <= t)`` rather than ``x > t``
    so NaN goes right, matching searchsorted's NaN-to-last-bin policy at
    training time."""
    node = jnp.zeros(X.shape[0], jnp.int32)
    for level in range(max_depth):
        offset = 2**level - 1
        heap_pos = offset + node
        # gather-free heap and feature lookups (see _indicator_lookup;
        # constant features carry inf thresholds and unselected X
        # columns may be NaN — the selects keep them inert while a
        # SELECTED NaN still routes right, the missing-value policy)
        feature = _indicator_lookup(heap_pos, features_heap)
        threshold = _indicator_lookup(heap_pos, thresholds_heap, fill=0.0)
        picked = jnp.maximum(feature, 0)[:, None] == jnp.arange(
            X.shape[1], dtype=jnp.int32
        )
        x = jnp.where(picked, X, 0.0).sum(axis=1)
        go_right = ~(x <= threshold) & (feature >= 0)
        node = node * 2 + go_right.astype(jnp.int32)
    return node


def _heap_thresholds(features_heap, bins_heap, thresholds):
    """Float threshold per internal node: ``thresholds[f, b]``. A split
    at the last bin can never be selected (its right side is empty), so
    ``b`` is always a valid threshold index."""
    safe_feature = jnp.maximum(features_heap, 0)
    safe_bin = jnp.minimum(bins_heap, thresholds.shape[1] - 1)
    return thresholds[safe_feature, safe_bin]


# --------------------------------------------------------------------------
# Estimators
# --------------------------------------------------------------------------

class _TreeEnsembleModel(FittedModel):
    """Shared predict machinery: stacked heaps (T, 2^D-1) + leaf stats."""

    def __init__(self, features_heap, thresholds_heap, leaf_probs, mesh, max_depth):
        self.features_heap = features_heap        # (T, 2^D - 1)
        self.thresholds_heap = thresholds_heap    # (T, 2^D - 1)
        self.leaf_probs = leaf_probs              # (T, 2^D, C)
        self.mesh = mesh
        self.max_depth = max_depth

    def _device_eval(self, X):
        X_dev, _, mask = prepare_xy(X, None, self.mesh)
        probs = _ensemble_forward(
            X_dev,
            self.features_heap,
            self.thresholds_heap,
            self.leaf_probs,
            self.max_depth,
        )
        return jnp.argmax(probs, axis=1), probs, mask


@partial(jax.jit, static_argnames=("max_depth",))
def _ensemble_forward(X, features_heap, thresholds_heap, leaf_probs, max_depth):
    """Mean class distribution over trees, sequentially accumulated.

    NOT a vmap over trees: that materializes a ``(trees, rows, classes)``
    intermediate whose class-minor dimension pads to the 128-lane tile —
    at 20 trees × 10M rows that is ~100 GB of HBM for 1.6 GB of data.
    The scan keeps one ``(classes, rows)`` accumulator (rows minor → no
    padding) and one tree's gather live at a time."""
    num_classes = leaf_probs.shape[-1]

    def one_tree(acc, tree):
        features, thresholds, leaves = tree
        leaf = _descend(X, features, thresholds, max_depth)
        return acc + leaves.T[:, leaf], None

    acc, _ = jax.lax.scan(
        one_tree,
        jnp.zeros((num_classes, X.shape[0]), jnp.float32),
        (features_heap, thresholds_heap, leaf_probs),
    )
    if features_heap.shape[0] == 0:  # numTrees=0: uniform, not 0/0 NaN
        return jnp.full((X.shape[0], num_classes), 1.0 / num_classes)
    return (acc / features_heap.shape[0]).T


@partial(jax.jit, static_argnames=("num_classes", "max_depth", "max_bins"))
def _dt_fit(bins, y, weights, num_classes, max_depth, max_bins):
    one_hot = jax.nn.one_hot(y, num_classes, dtype=jnp.float32) * weights[:, None]
    return _fit_classification_tree(bins, one_hot, max_depth, max_bins)


def _rf_specs(mesh):
    return (
        NamedSharding(mesh, P(MODEL_AXIS, None)),       # features heap
        NamedSharding(mesh, P(MODEL_AXIS, None)),       # split-bin heap
        NamedSharding(mesh, P(MODEL_AXIS, None, None)), # leaf probs
    )


@partial(
    jax.jit,
    static_argnames=("num_classes", "max_depth", "max_bins", "subset_k", "mesh"),
)
def _rf_chunk(
    bins, y, weights, keys, num_classes, max_depth, max_bins, subset_k,
    mesh=None,
):
    base_one_hot = jax.nn.one_hot(y, num_classes, dtype=jnp.float32)

    def one_tree(tree_key):
        bootstrap_key, subset_key = jax.random.split(tree_key)
        bootstrap = jax.random.poisson(
            bootstrap_key, 1.0, (bins.shape[0],)
        ).astype(jnp.float32)
        one_hot = base_one_hot * (weights * bootstrap)[:, None]
        return _fit_classification_tree(
            bins, one_hot, max_depth, max_bins, subset_key, subset_k
        )

    # Tensor parallelism over TREES: the vmap axis is sharded on the
    # mesh's model axis (when it divides evenly), so a (data, model)
    # mesh grows trees 2D-parallel — each device builds the histograms
    # for its tree shard over its row shard, and XLA psums the
    # histograms over the data axis only. Uneven splits replicate, like
    # LR's class axis.
    specs = None
    if mesh is not None and keys.shape[0] % model_size(mesh) == 0:
        specs = _rf_specs(mesh)
        keys = jax.lax.with_sharding_constraint(
            keys, NamedSharding(mesh, P(MODEL_AXIS))
        )
    out = jax.vmap(one_tree)(keys)
    if specs is not None:
        out = tuple(
            jax.lax.with_sharding_constraint(array, spec)
            for array, spec in zip(out, specs)
        )
    return out


# Per-program budget in row*trees: one bootstrap tree costs about one
# boosting round — ~4 trees at 10M rows keeps a segment short (see
# base.segment_steps; PERF.md §5 has a program's seconds in each cell).
_RF_ROW_TREES_BUDGET = 40e6

# HBM cap on the vmap width: a chunk's per-tree row operands (the
# weighted class one-hots, padded to the 128-lane tile: ~512 B/row at
# f32) — 20M row*trees per device ≈ 10 GB, inside a 16 GB v5e alongside
# the binned matrix. The level histograms themselves hold one row tile
# at a time (hist_block_plan), whatever the vmap width.
_RF_ROW_TREES_PER_DEVICE_HBM = 20e6


def _rf_fit(
    bins, y, weights, key, num_classes, max_depth, max_bins, num_trees,
    subset_k, mesh=None,
):
    """Forest fit in watchdog- and HBM-safe chunks of trees. Trees are
    independent, so chunking only splits the vmap width; the key fan-out
    matches the former single-program fit, and on a model-sharded mesh
    the chunk width stays a multiple of the model axis so every chunk
    keeps the 2D tree/row parallelism."""
    from learningorchestra_tpu.ml.base import largest_divisor, segment_steps
    from learningorchestra_tpu.parallel.mesh import data_size

    if num_trees <= 0:  # empty forest: empty heaps (vmap over no keys)
        return _rf_chunk(
            bins, y, weights, jax.random.split(key, 0), num_classes,
            max_depth, max_bins, subset_k, None,
        )
    chunk = segment_steps(
        num_trees, bins.shape[0], _RF_ROW_TREES_BUDGET, bins.shape[1]
    )
    rows_per_device = bins.shape[0] // (data_size(mesh) if mesh else 1)
    hbm_chunk = max(1, int(_RF_ROW_TREES_PER_DEVICE_HBM // max(rows_per_device, 1)))
    if hbm_chunk < chunk:
        chunk = largest_divisor(num_trees, hbm_chunk)
    sharded = mesh is not None and num_trees % model_size(mesh) == 0
    if sharded and chunk % model_size(mesh) != 0:
        width = model_size(mesh)
        chunk = largest_divisor(num_trees, max(chunk, width), multiple_of=width)
    keys = jax.random.split(key, num_trees)
    chunks = [
        _rf_chunk(
            bins, y, weights, keys[start : start + chunk], num_classes,
            max_depth, max_bins, subset_k, mesh,
        )
        for start in range(0, num_trees, chunk)
    ]
    if len(chunks) == 1:
        return chunks[0]
    out = tuple(jnp.concatenate(parts) for parts in zip(*chunks))
    if sharded:
        out = tuple(
            jax.device_put(array, spec)
            for array, spec in zip(out, _rf_specs(mesh))
        )
    return out


@jax.jit
def _gbt_init(y, weights):
    y_f = y.astype(jnp.float32)
    n_real = jnp.maximum(weights.sum(), 1.0)
    base_rate = jnp.clip((y_f * weights).sum() / n_real, 1e-6, 1 - 1e-6)
    f0 = jnp.log(base_rate / (1 - base_rate))
    return f0, jnp.full(y.shape[0], f0, jnp.float32)


def _gbt_rounds_impl(
    bins, y, weights, margins, max_depth, max_bins, rounds, step
):
    """``rounds`` boosting rounds as one program, margins in and out —
    chained by :func:`_gbt_fit` (see base.segment_steps)."""
    y_f = y.astype(jnp.float32)

    def one_round(margins, _):
        p = jax.nn.sigmoid(margins)
        g = (p - y_f) * weights
        h = jnp.maximum(p * (1 - p), 1e-6) * weights
        features, split_bins, leaf_values, leaf_of_row = _fit_newton_tree(
            bins, g, h, max_depth, max_bins
        )
        margins = margins + step * leaf_values[leaf_of_row]
        return margins, (features, split_bins, leaf_values)

    margins, (features_heap, bins_heap, leaf_values) = jax.lax.scan(
        one_round, margins, length=rounds
    )
    return margins, features_heap, bins_heap, leaf_values


_gbt_rounds = partial(
    jax.jit, static_argnames=("max_depth", "max_bins", "rounds")
)(_gbt_rounds_impl)


@lru_cache(maxsize=None)
def _donated_gbt_rounds():
    return jax.jit(
        _gbt_rounds_impl,
        static_argnames=("max_depth", "max_bins", "rounds"),
        donate_argnums=(3,),
    )


def _gbt_rounds_runner():
    """The segment program :func:`_gbt_fit` chains: the margin vector
    (argument 3) is DONATED — each segment's output margins rebind it,
    so XLA reuses that (rows,)-sized HBM buffer across boosting
    segments instead of holding two generations per boundary
    (``donate_argnums``, SNIPPETS.md [3]). bins/y/weights are re-read
    every segment and stay undonated. CPU backends don't implement
    donation and use the shared undonated program, read as the MODULE
    attribute at call time (so tests can script it; resolving lazily
    also means importing this module never initializes the device
    backend)."""
    if jax.default_backend() == "cpu":
        return _gbt_rounds
    return _donated_gbt_rounds()


# Per-program budget in row*rounds: one boosting round builds a whole
# depth-5 tree, so ~4 rounds at 10M rows keeps a segment short (see
# base.segment_steps; PERF.md §5 has a program's seconds in each cell).
_GB_ROW_ROUNDS_BUDGET = 40e6


def _gbt_fit(bins, y, weights, max_depth, max_bins, rounds, step):
    """Sequential boosting in watchdog-safe segments; the margin vector
    carries across programs, so the round sequence matches the former
    single-scan program."""
    from learningorchestra_tpu.ml.base import segment_steps

    f0, margins = _gbt_init(y, weights)
    if rounds <= 0:  # zero rounds: empty heaps, base-rate-only model
        _, features_heap, bins_heap, leaf_values = _gbt_rounds(
            bins, y, weights, margins, max_depth, max_bins, 0, step
        )
        return f0, features_heap, bins_heap, leaf_values
    chunk = segment_steps(
        rounds, bins.shape[0], _GB_ROW_ROUNDS_BUDGET, bins.shape[1]
    )
    heaps = []
    rounds_chunk = _gbt_rounds_runner()
    total_chunks = rounds // chunk
    # Crash resume (see ml/progress.py): margins + the heaps built so
    # far are enough to replay the remaining chunks bit-identically —
    # f0 is recomputed deterministically from y/weights above. The
    # artifact must match this call's chunking and hyperparameters on
    # top of the sink's rev/dtype/mesh key, else restart clean.
    scalars = {
        "chunk": chunk,
        "rounds": rounds,
        "max_depth": max_depth,
        "max_bins": max_bins,
        "step": float(np.asarray(step)),
    }
    start = 0
    sink = _progress.current_sink()
    if sink is not None:
        restored = sink.load("gbt")
        if restored is not None:
            done, arrays, saved = restored
            state = None
            if (
                all(saved.get(key) == scalars[key] for key in scalars)
                and 0 < done <= total_chunks
                and len(arrays) == 4
                and all(a.shape[0] == done * chunk for a in arrays[1:])
            ):
                state = _progress.device_restore(margins, [arrays[0]])
            if state is None:
                sink.discard()
            else:
                margins = state
                heaps.append(tuple(jnp.asarray(a) for a in arrays[1:]))
                start = done
                _progress.segments_skipped(done)
    for index in range(start, total_chunks):
        margins, features_heap, bins_heap, leaf_values = rounds_chunk(
            bins, y, weights, margins, max_depth, max_bins, chunk, step
        )
        heaps.append((features_heap, bins_heap, leaf_values))
        if sink is not None:
            sink.save(
                "gbt",
                index + 1,
                [np.asarray(margins)]
                + [
                    np.concatenate([np.asarray(h[i]) for h in heaps])
                    for i in range(3)
                ],
                scalars,
            )
    if len(heaps) == 1:
        features_heap, bins_heap, leaf_values = heaps[0]
    else:
        features_heap, bins_heap, leaf_values = (
            jnp.concatenate(parts) for parts in zip(*heaps)
        )
    return f0, features_heap, bins_heap, leaf_values


@partial(jax.jit, static_argnames=("max_depth",))
def _gbt_forward(X, f0, features_heap, thresholds_heap, leaf_values, step, max_depth):
    """Boosted margins, sequentially accumulated over rounds — like
    :func:`_ensemble_forward`, NOT a vmap over trees: the batched
    ``(rounds, rows)`` descend intermediates pad ~6x on TPU tile
    boundaries (25 GB at 20×10M rows); the scan keeps one margin
    vector and one round's gather live at a time."""

    def one_tree(margins, tree):
        features, thresholds, leaves = tree
        leaf = _descend(X, features, thresholds, max_depth)
        return margins + step * leaves[leaf], None

    margins, _ = jax.lax.scan(
        one_tree,
        jnp.full(X.shape[0], f0, jnp.float32),
        (features_heap, thresholds_heap, leaf_values),
    )
    p = jax.nn.sigmoid(margins)
    return jnp.stack([1 - p, p], axis=1)


# ``fit:enqueue`` in the three fits below: the host handing the fit's
# programs to the device. Milliseconds when the device's queue has room;
# the runtime keeps at most 32 programs in flight, and the 33rd dispatch
# (every eager slice, gather and cast is one) blocks its thread until
# the program at the head finishes, as does a readback of a fresh value.
# With ``fit:device_wait`` it is the fit waiting its turn on the device.


def _traced_thresholds(X, X_dev, mask, max_bins: int, mesh) -> np.ndarray:
    """The fit's bin thresholds under a ``fit:thresholds`` span: the
    device quantile pass over the matrix the fit has just put there
    (``passes`` 1), or the wait for the pass another fit of the same
    build runs on the same host matrix (``passes`` 0). The span of the
    fit that ran the pass also says how many of its ``features x
    (max_bins - 1)`` thresholds differ (``distinct_thresholds``)."""
    with _tracing.span(
        "fit:thresholds", rows=len(X), features=X_dev.shape[1], bins=max_bins
    ):
        thresholds, passes = shared_thresholds(X, X_dev, mask, max_bins, mesh)
        _tracing.annotate(passes=passes)
        if passes:
            _tracing.annotate(distinct_thresholds=distinct_thresholds(thresholds))
        return thresholds


class DecisionTreeClassifier:
    def __init__(
        self,
        max_depth: int = MAX_DEPTH,
        max_bins: int = MAX_BINS,
        mesh: Optional[Mesh] = None,
    ):
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.mesh = resolve_mesh(mesh)

    def fit(self, X: np.ndarray, y: np.ndarray) -> _TreeEnsembleModel:
        num_classes = infer_num_classes(y)
        X_dev, y_dev, mask = prepare_xy(X, y, self.mesh)
        thresholds = _traced_thresholds(
            X, X_dev, mask, self.max_bins, self.mesh
        )
        with _tracing.span(
            "fit:enqueue", **hist_block_plan(*X_dev.shape, self.max_bins)
        ):
            bins = apply_bins(X_dev, jnp.asarray(thresholds, jnp.float32))
            features_heap, bins_heap, leaf_probs = _dt_fit(
                bins,
                y_dev,
                mask.astype(jnp.float32),
                num_classes,
                self.max_depth,
                self.max_bins,
            )
            thresholds_heap = _heap_thresholds(
                features_heap, bins_heap, jnp.asarray(thresholds, jnp.float32)
            )
            return _TreeEnsembleModel(
                features_heap[None],
                thresholds_heap[None],
                leaf_probs[None],
                self.mesh,
                self.max_depth,
            )


class RandomForestClassifier:
    def __init__(
        self,
        num_trees: int = NUM_TREES,
        max_depth: int = MAX_DEPTH,
        max_bins: int = MAX_BINS,
        seed: int = 0,
        mesh: Optional[Mesh] = None,
    ):
        self.num_trees = num_trees
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.seed = seed
        self.mesh = resolve_mesh(mesh)

    def fit(self, X: np.ndarray, y: np.ndarray) -> _TreeEnsembleModel:
        num_classes = infer_num_classes(y)
        num_features = np.asarray(X).shape[1]
        subset_k = max(1, int(np.ceil(np.sqrt(num_features))))
        X_dev, y_dev, mask = prepare_xy(X, y, self.mesh)
        thresholds = _traced_thresholds(
            X, X_dev, mask, self.max_bins, self.mesh
        )
        with _tracing.span(
            "fit:enqueue",
            subset_k=subset_k,
            **hist_block_plan(*X_dev.shape, self.max_bins),
        ):
            bins = apply_bins(X_dev, jnp.asarray(thresholds, jnp.float32))
            features_heap, bins_heap, leaf_probs = _rf_fit(
                bins,
                y_dev,
                mask.astype(jnp.float32),
                jax.random.key(self.seed),
                num_classes,
                self.max_depth,
                self.max_bins,
                self.num_trees,
                subset_k,
                mesh=self.mesh,
            )
            thresholds_heap = _heap_thresholds(
                features_heap, bins_heap, jnp.asarray(thresholds, jnp.float32)
            )
            return _TreeEnsembleModel(
                features_heap,
                thresholds_heap,
                leaf_probs,
                self.mesh,
                self.max_depth,
            )


class GBTModel(FittedModel):
    def __init__(self, f0, features_heap, thresholds_heap, leaf_values, step, mesh, max_depth):
        self.f0 = f0
        self.features_heap = features_heap
        self.thresholds_heap = thresholds_heap
        self.leaf_values = leaf_values
        self.step = step
        self.mesh = mesh
        self.max_depth = max_depth

    def _device_eval(self, X):
        X_dev, _, mask = prepare_xy(X, None, self.mesh)
        probs = _gbt_forward(
            X_dev,
            self.f0,
            self.features_heap,
            self.thresholds_heap,
            self.leaf_values,
            jnp.float32(self.step),
            self.max_depth,
        )
        return jnp.argmax(probs, axis=1), probs, mask


class GBTClassifier:
    """Binary gradient-boosted trees (MLlib GBTClassifier is binary-only)."""

    def __init__(
        self,
        rounds: int = GBT_ROUNDS,
        step: float = GBT_STEP,
        max_depth: int = MAX_DEPTH,
        max_bins: int = MAX_BINS,
        mesh: Optional[Mesh] = None,
    ):
        self.rounds = rounds
        self.step = step
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.mesh = resolve_mesh(mesh)

    def fit(self, X: np.ndarray, y: np.ndarray) -> GBTModel:
        if infer_num_classes(y) > 2:
            raise ValueError("GBTClassifier supports binary labels only (MLlib contract)")
        X_dev, y_dev, mask = prepare_xy(X, y, self.mesh)
        thresholds = _traced_thresholds(
            X, X_dev, mask, self.max_bins, self.mesh
        )
        with _tracing.span(
            "fit:enqueue", **hist_block_plan(*X_dev.shape, self.max_bins)
        ):
            bins = apply_bins(X_dev, jnp.asarray(thresholds, jnp.float32))
            f0, features_heap, bins_heap, leaf_values = _gbt_fit(
                bins,
                y_dev,
                mask.astype(jnp.float32),
                self.max_depth,
                self.max_bins,
                self.rounds,
                jnp.float32(self.step),
            )
            thresholds_heap = _heap_thresholds(
                features_heap, bins_heap, jnp.asarray(thresholds, jnp.float32)
            )
            return GBTModel(
                f0,
                features_heap,
                thresholds_heap,
                leaf_values,
                self.step,
                self.mesh,
                self.max_depth,
            )
