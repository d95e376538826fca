"""The plain reference: the five MLlib-default classifiers' semantics in
float64 numpy. It imports nothing of ``learningorchestra_tpu`` and takes
no weights, scales or tables from it.

Two kinds of function live here.

- Fits that have one right answer given the data are made from scratch:
  multinomial naive Bayes (closed form), logistic regression without a
  penalty (convex, so Newton's method finds the optimum that L-BFGS is
  converging to, whatever its path), and the greedy histogram decision
  tree (quantile candidates, best gini gain at every node).
- An ensemble's fit has no single right answer: near-tied gains flip on
  the last bit of a sum, and the forest's bootstrap is the fitter's own
  random stream. So every published tree is *audited* from the data
  alone: the rows are sent down the published splits, and at every node
  the float64 gain of the published split is held against the best over
  the reference's own quantile candidates; every leaf's class shares,
  and every boosting round's Newton leaf values from the gradients the
  earlier rounds leave, are recomputed from all training rows.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 19
THREADS = 8


def as_matrix(columns: list[np.ndarray], dtype=np.float64) -> np.ndarray:
    # column-major: a column lands as one copy, and the trees' per-column
    # gathers stay local
    out = np.empty((len(columns[0]), len(columns)), dtype=dtype, order="F")
    for j, column in enumerate(columns):
        out[:, j] = column
    return out


# --- naive Bayes (multinomial, smoothing 1) -------------------------------

def _blocks(n: int):
    return [(start, min(start + BLOCK, n)) for start in range(0, n, BLOCK)]


def _over_blocks(function, n: int):
    """``function(start, stop)`` over row blocks on a few threads (numpy
    releases the lock inside its loops); the results in block order."""
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return list(pool.map(lambda span: function(*span), _blocks(n)))


def nb_fit(X: np.ndarray, y: np.ndarray, smoothing: float = 1.0):
    def sums(start, stop):
        block = X[start:stop].astype(np.float64)
        positive = y[start:stop].astype(np.float64)
        total = block.sum(axis=0)
        ones = positive @ block
        return np.stack([total - ones, ones]), np.array(
            [len(positive) - positive.sum(), positive.sum()]
        )

    parts = _over_blocks(sums, len(X))
    feature_sums = sum(p[0] for p in parts)
    counts = sum(p[1] for p in parts)
    smoothed = feature_sums + smoothing
    theta = np.log(smoothed) - np.log(smoothed.sum(axis=1, keepdims=True))
    prior = np.log(counts) - np.log(counts.sum())
    return theta, prior


def nb_proba(model, X: np.ndarray) -> np.ndarray:
    theta, prior = model
    joint = X.astype(np.float64) @ theta.T + prior
    joint -= joint.max(axis=1, keepdims=True)
    e = np.exp(joint)
    return e / e.sum(axis=1, keepdims=True)


# --- logistic regression (no penalty, intercept) --------------------------

def lr_fit(X: np.ndarray, y: np.ndarray, iterations: int = 12, tol: float = 1e-9):
    """Newton's method in float64 on the raw columns with an intercept:
    without a penalty the optimum is the one that L-BFGS on standardised
    columns converges to."""
    n, d = X.shape
    w = np.zeros(d + 1)

    def pass_over(start, stop):
        Z = np.empty((stop - start, d + 1))
        Z[:, :d] = X[start:stop]
        Z[:, d] = 1.0
        p = 1.0 / (1.0 + np.exp(-(Z @ w)))
        grad = Z.T @ (p - y[start:stop])
        hess = (Z * (p * (1 - p))[:, None]).T @ Z
        return grad, hess

    for _ in range(iterations):
        parts = _over_blocks(pass_over, n)
        grad = sum(p[0] for p in parts)
        hess = sum(p[1] for p in parts)
        step = np.linalg.solve(hess, grad)
        w -= step
        if np.abs(step).max() < tol * max(1.0, np.abs(w).max()):
            break
    return w[:d], w[d]


def lr_proba(model, X: np.ndarray) -> np.ndarray:
    coef, intercept = model
    p = 1.0 / (1.0 + np.exp(-(X.astype(np.float64) @ coef + intercept)))
    return np.stack([1 - p, p], axis=1)


# --- published trees, followed --------------------------------------------

def route(X: np.ndarray, features: np.ndarray, thresholds: np.ndarray, depth: int) -> np.ndarray:
    """Leaf index of every row under one heap tree: a value at or under
    the threshold goes left, a NaN goes right, and a node whose feature
    is -1 sends everything left. ``X`` keeps its own dtype, so a float32
    value is compared with a float32 threshold as published."""
    node = np.zeros(len(X), dtype=np.int64)
    rows = np.arange(len(X))
    for level in range(depth):
        heap = (1 << level) - 1 + node
        feature = features[heap]
        value = X[rows, np.maximum(feature, 0)]
        right = ~(value <= thresholds[heap].astype(X.dtype)) & (feature >= 0)
        node = node * 2 + right
    return node


def ensemble_proba(X, features_heap, thresholds_heap, leaf_probs, depth):
    total = np.zeros((len(X), leaf_probs.shape[-1]))
    for t in range(len(features_heap)):
        leaf = route(X, features_heap[t], thresholds_heap[t], depth)
        total += leaf_probs[t][leaf]
    return total / max(len(features_heap), 1)


def gbt_margins(X, f0, step, features_heap, thresholds_heap, leaf_values, depth):
    margins = np.full(len(X), float(f0))
    for t in range(len(features_heap)):
        leaf = route(X, features_heap[t], thresholds_heap[t], depth)
        margins += float(step) * leaf_values[t][leaf].astype(np.float64)
    return margins


def gbt_proba(X, f0, step, features_heap, thresholds_heap, leaf_values, depth):
    p = 1.0 / (1.0 + np.exp(-gbt_margins(X, f0, step, features_heap, thresholds_heap, leaf_values, depth)))
    return np.stack([1 - p, p], axis=1)


# --- quantile candidates and histograms -----------------------------------

def _over(function, items):
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return list(pool.map(function, items))


def quantile_thresholds(X: np.ndarray, max_bins: int) -> np.ndarray:
    """The candidate splits: per column the ``max_bins - 1`` interior
    quantiles of the float64 values, as float32 - ``(features, bins-1)``."""
    points = np.linspace(0, 1, max_bins + 1)[1:-1]

    def one(j):
        return np.quantile(X[:, j].astype(np.float64), points)

    return np.stack(_over(one, range(X.shape[1]))).astype(np.float32)


def bin_matrix(X: np.ndarray, thresholds: np.ndarray) -> np.ndarray:
    """Bin of every value: how many of its column's thresholds lie under
    it, so that ``bin <= b`` says ``x <= thresholds[b]``. int8, column-major."""
    out = np.empty(X.shape, dtype=np.int8, order="F")

    def one(j):
        out[:, j] = np.searchsorted(thresholds[j], X[:, j], side="left")

    _over(one, range(X.shape[1]))
    return out


def histograms(bins: np.ndarray, group: np.ndarray, weights: list, n_groups: int, max_bins: int):
    """Sums of each weight vector by ``(group, feature, bin)``:
    ``(len(weights), n_groups, features, max_bins)`` in float64."""
    base = group.astype(np.int32) * max_bins
    size = n_groups * max_bins

    def one(j):
        index = base + bins[:, j]
        return [np.bincount(index, weights=w, minlength=size) for w in weights]

    parts = _over(one, range(bins.shape[1]))
    out = np.array(parts).reshape(bins.shape[1], len(weights), n_groups, max_bins)
    return out.transpose(1, 2, 0, 3)


def gini_score(counts: np.ndarray) -> np.ndarray:
    """``sum_c n_c^2 / n`` of class counts on the last axis."""
    return (counts**2).sum(-1) / np.maximum(counts.sum(-1), 1e-300)


def gini_gains(hist: np.ndarray) -> np.ndarray:
    """Gain of every candidate from class histograms ``(classes, nodes,
    features, bins)``: ``(nodes, features, bins)``; a candidate with an
    empty side is -inf."""
    left = np.cumsum(hist, axis=3).transpose(1, 2, 3, 0)
    total = left[:, :, -1:, :]
    right = total - left
    gain = gini_score(left) + gini_score(right) - gini_score(total)
    valid = (left.sum(-1) > 0) & (right.sum(-1) > 0)
    return np.where(valid, gain, -np.inf)


def newton_score(G, H, lam):
    return G**2 / (H + lam)


def newton_gains(hist: np.ndarray, lam: float) -> np.ndarray:
    """Gain of every candidate from ``(g, h)`` histograms ``(2, nodes,
    features, bins)``."""
    left = np.cumsum(hist, axis=3)
    total = left[:, :, :, -1:]
    right = total - left
    gain = (
        newton_score(left[0], left[1], lam) + newton_score(right[0], right[1], lam)
        - newton_score(total[0], total[1], lam)
    )
    valid = (left[1] > 1e-12) & (right[1] > 1e-12)
    return np.where(valid, gain, -np.inf)


def _fold(deepest: np.ndarray, depth: int) -> list:
    """Per-level histograms from the one taken at the deepest internal
    level: a node's histogram is the sum of its descendants'."""
    levels = [deepest]
    for _ in range(depth - 1):
        last = levels[-1]
        levels.append(last[:, 0::2] + last[:, 1::2])
    return levels[::-1]


def route_blocks(X, features, thresholds, depth) -> np.ndarray:
    """:func:`route` over row blocks on a few threads."""
    parts = _over_blocks(
        lambda start, stop: route(X[start:stop], features, thresholds, depth), len(X)
    )
    return np.concatenate(parts)


def audit_tree(bins, leaf, features, channels, kind, depth, max_bins, lam=0.0):
    """One published tree against the data. ``leaf`` is every row's leaf
    under the published splits, ``channels`` the per-row weight vectors
    (class indicators times the row weight for ``gini``; g and h for
    ``newton``). Returns, per internal heap node: the best gain over the
    reference's candidates, the best over the candidates of the
    published feature alone, the gain of the published split (from the
    sums of its two children, so it needs no candidate to match), the
    node's weight; and the channels' sums at the leaves."""
    n_deep = 1 << (depth - 1)
    hist = histograms(bins, leaf >> 1, channels, n_deep, max_bins)
    score = (lambda s: gini_score(np.moveaxis(s, 0, -1))) if kind == "gini" else (
        lambda s: newton_score(s[0], s[1], lam)
    )
    best, own, chosen, weight = [], [], [], []
    at_leaves = leaf_sums(leaf, channels, depth)
    sums = [at_leaves]
    for _ in range(depth):
        sums.append(sums[-1][:, 0::2] + sums[-1][:, 1::2])
    sums = sums[::-1]  # sums[level]: (channels, 2**level)
    for level, level_hist in enumerate(_fold(hist, depth)):
        gains = gini_gains(level_hist) if kind == "gini" else newton_gains(level_hist, lam)
        best.append(gains.reshape(gains.shape[0], -1).max(axis=1))
        children = score(sums[level + 1])
        split = children[0::2] + children[1::2] - score(sums[level])
        heap = (1 << level) - 1 + np.arange(1 << level)
        picked = gains[np.arange(1 << level), np.maximum(features[heap], 0)].max(axis=1)
        own.append(np.where(features[heap] >= 0, picked, 0.0))
        chosen.append(np.where(features[heap] >= 0, split, 0.0))
        weight.append(sums[level].sum(axis=0) if kind == "gini" else sums[level][1])
    return (
        np.concatenate(best), np.concatenate(own), np.concatenate(chosen),
        np.concatenate(weight), at_leaves,
    )


def grow_tree(bins, thresholds, channels, kind, depth, max_bins, lam=0.0,
              allowed=None):
    """The greedy histogram fit, level by level: at every node the
    candidate of the largest gain; a node whose best gain is not positive
    stays a leaf (feature -1). ``allowed(level)`` may give a boolean
    ``(nodes, features)`` mask - a forest's per-node feature subset.
    Returns heap features, heap thresholds and every row's leaf."""
    node = np.zeros(len(bins), dtype=np.int32)
    features_heap, thresholds_heap = [], []
    for level in range(depth):
        hist = histograms(bins, node, channels, 1 << level, max_bins)
        gains = gini_gains(hist) if kind == "gini" else newton_gains(hist, lam)
        if allowed is not None:
            gains = np.where(allowed(level)[:, :, None], gains, -np.inf)
        flat = gains.reshape(gains.shape[0], -1)
        pick = flat.argmax(axis=1)
        top = flat[np.arange(len(pick)), pick]
        feature = np.where(top > 0, pick // max_bins, -1)
        split_bin = np.minimum(pick % max_bins, max_bins - 2)
        features_heap.append(feature)
        thresholds_heap.append(thresholds[np.maximum(feature, 0), split_bin])
        row_feature = feature[node]
        value = bins[np.arange(len(bins)), np.maximum(row_feature, 0)]
        node = node * 2 + ((value > split_bin[node]) & (row_feature >= 0))
    return (
        np.concatenate(features_heap).astype(np.int32),
        np.concatenate(thresholds_heap).astype(np.float32),
        node,
    )


def class_channels(y: np.ndarray, weight=None, classes: int = 2) -> list:
    weight = np.ones(len(y)) if weight is None else weight.astype(np.float64)
    return [weight * (y == c) for c in range(classes)]


def leaf_shares(leaf_sums: np.ndarray):
    """Class shares and weight of every leaf from ``(classes, leaves)``."""
    total = leaf_sums.sum(axis=0)
    return (leaf_sums / np.maximum(total, 1e-300)).T, total


def leaf_sums(leaf: np.ndarray, channels: list, depth: int) -> np.ndarray:
    """Each weight vector summed by leaf: ``(len(channels), leaves)``."""
    return np.array(
        _over(lambda w: np.bincount(leaf, weights=w, minlength=1 << depth), channels)
    )


def grow_model(bins, thresholds, y, max_bins: int, depth: int, weight=None, allowed=None):
    """The reference's own decision tree, in the published layout."""
    channels = class_channels(y, weight)
    features, cuts, leaf = grow_tree(
        bins, thresholds, channels, "gini", depth, max_bins, allowed=allowed
    )
    shares, _ = leaf_shares(leaf_sums(leaf, channels, depth))
    return {
        "kind": "tree_ensemble", "max_depth": depth,
        "features_heap": features[None], "thresholds_heap": cuts[None],
        "leaf_probs": shares[None],
    }


def grow_forest(bins, thresholds, y, max_bins: int, depth: int, trees: int, seed: int,
                allowed=None):
    """A forest as MLlib's defaults make it: a Poisson(1) bootstrap per
    tree and, at every node, the best of a random sqrt-sized subset of
    the features (or of ``allowed``, where a planted fault says so). The
    random stream is this function's own."""
    rng = np.random.default_rng([int(seed), 86028121])
    width = bins.shape[1]
    subset = max(1, int(np.ceil(np.sqrt(width))))
    grown = []
    for _ in range(trees):
        weight = rng.poisson(1.0, len(bins)).astype(np.float64)

        def subsets(level):
            order = rng.random((1 << level, width)).argsort(axis=1)
            return order < subset

        grown.append(
            grow_model(bins, thresholds, y, max_bins, depth, weight, allowed or subsets)
        )
    return {
        "kind": "tree_ensemble", "max_depth": depth,
        **{
            key: np.concatenate([tree[key] for tree in grown])
            for key in ("features_heap", "thresholds_heap", "leaf_probs")
        },
    }


def grow_boosted(bins, thresholds, y, max_bins, depth, rounds, step, lam, hessian_floor,
                 allowed=None):
    """Newton boosting of the logistic loss from the base rate's margin."""
    y = y.astype(np.float64)
    rate = np.clip(y.mean(), 1e-6, 1 - 1e-6)
    f0 = float(np.log(rate / (1 - rate)))
    margins = np.full(len(y), f0)
    grown = []
    for _ in range(rounds):
        p = 1.0 / (1.0 + np.exp(-margins))
        channels = [p - y, np.maximum(p * (1 - p), hessian_floor)]
        features, cuts, leaf = grow_tree(
            bins, thresholds, channels, "newton", depth, max_bins, lam, allowed
        )
        sums = leaf_sums(leaf, channels, depth)
        values = -sums[0] / (sums[1] + lam)
        margins += step * values[leaf]
        grown.append((features, cuts, values))
    return {
        "kind": "gbt", "max_depth": depth, "f0": f0, "step": float(step),
        "features_heap": np.stack([g[0] for g in grown]),
        "thresholds_heap": np.stack([g[1] for g in grown]),
        "leaf_values": np.stack([g[2] for g in grown]),
    }


def logloss(p1: np.ndarray, y: np.ndarray) -> float:
    p = np.clip(p1.astype(np.float64), 1e-7, 1 - 1e-7)
    return float(-(y * np.log(p) + (1 - y) * np.log(1 - p)).mean())


def to_bfloat16(X: np.ndarray) -> np.ndarray:
    """The control's precision: float32 values rounded to bfloat16 and
    widened again, as a bfloat16 feature matrix holds them."""
    import ml_dtypes

    return X.astype(ml_dtypes.bfloat16).astype(np.float32)


def accuracy_f1(labels: np.ndarray, truth: np.ndarray, classes: int = 2):
    """Accuracy and MLlib's weighted F1."""
    accuracy = float((labels == truth).mean())
    f1 = 0.0
    for c in range(classes):
        tp = float(((labels == c) & (truth == c)).sum())
        fp = float(((labels == c) & (truth != c)).sum())
        fn = float(((labels != c) & (truth == c)).sum())
        precision = tp / (tp + fp) if tp + fp else 0.0
        recall = tp / (tp + fn) if tp + fn else 0.0
        score = (
            2 * precision * recall / (precision + recall)
            if precision + recall
            else 0.0
        )
        f1 += score * float((truth == c).mean())
    return accuracy, f1
