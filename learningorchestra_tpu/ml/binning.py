"""Feature binning: quantile thresholds + on-device bin assignment.

Trees on TPU want histogram-binned features: exact split search over raw
floats is data-dependent control flow, but binned split search is a dense
scatter/cumsum program with static shapes. Same trick Spark MLlib itself
uses (``maxBins=32`` default) and the reason its trees scale; here the
binning keeps every tree op on the MXU/VPU.

Bin semantics: ``bin b`` holds values ``thresholds[b-1] < x <=
thresholds[b]``; a split "at bin b" sends ``x <= thresholds[b]`` left, so
raw-feature prediction only needs the float threshold, never the bins.

Assignment (``apply_bins``) counts, per value, its feature's thresholds
below it: one fused elementwise program, no gather and no loop.

The thresholds are made on the device too, from the float32 matrix a
fit has put there (``device_thresholds``): exact order statistics by
rank selection, interpolated on the host as numpy does. The tree fits
of one build share one pass (``shared_thresholds``).
"""

from __future__ import annotations

import threading
import weakref
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from learningorchestra_tpu.parallel.multihost import fetch

MAX_BINS = 32

_KEY_BITS = 32
_NOT_COUNTED = np.uint32(0xFFFFFFFF)  # above the key of every finite float
_SIGN = np.uint32(0x80000000)
_INFINITY = np.uint32(0x7F800000)  # the bits of +inf; NaNs lie above


def _sortable_keys(X: jax.Array, mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(keys, counted)``: a ``uint32`` per value that orders as the
    float32 does (zeros of either sign alike), and which values count:
    the finite ones of the rows ``mask`` marks. The others get the one
    key above every finite float's."""
    # on the bits alone: float compares flush denormals to zero
    bits = jax.lax.bitcast_convert_type(X.astype(jnp.float32), jnp.uint32)
    bits = jnp.where(bits == _SIGN, jnp.uint32(0), bits)
    keys = jnp.where(bits >= _SIGN, ~bits, bits | _SIGN)
    counted = mask[:, None] & ((bits & ~_SIGN) < _INFINITY)
    return jnp.where(counted, keys, _NOT_COUNTED), counted


@jax.jit
def _finite_counts(X: jax.Array, mask: jax.Array) -> jax.Array:
    """Per column, how many values the quantiles are taken over."""
    _, counted = _sortable_keys(X, mask)
    return counted.sum(axis=0, dtype=jnp.int32)


@jax.jit
def _bin_order_statistics(
    X: jax.Array, mask: jax.Array, ranks: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Per column ``f`` and rank ``ranks[f, r]`` (0 the least) of its
    counted values: that order statistic and the next one up (the
    greatest twice), exactly, as sortable keys (``_key_values`` gives
    the floats), each of ``ranks.shape``.

    Selected, not sorted: the key of rank ``r`` is the largest ``v``
    with ``#{keys < v} <= r``, found a bit a step from the top. A step
    is one pass that compares every key with its column's candidates
    and sums over the rows: dense, no gather, no sort, and under a
    row-sharded mesh the sum is a plain reduction over the shards. Two
    more such passes give the next one up: the same value where more
    than ``r + 1`` keys are at most it, else the least key above.
    """
    with jax.named_scope("lo.quantile"):
        keys, _ = _sortable_keys(X, mask)
        keys = keys[:, :, None]

        def step(found, bit):
            candidates = found | (jnp.uint32(1) << bit)
            below = (keys < candidates).sum(axis=0, dtype=jnp.int32)
            return jnp.where(below <= ranks, candidates, found), None

        bits = jnp.arange(_KEY_BITS - 1, -1, -1, dtype=jnp.uint32)
        found, _ = jax.lax.scan(step, jnp.zeros(ranks.shape, jnp.uint32), bits)
        at_most = (keys <= found).sum(axis=0, dtype=jnp.int32)
        above = jnp.where(keys > found, keys, _NOT_COUNTED).min(axis=0)
        # no counted key above the greatest: it is its own neighbour
        above = jnp.where(above == _NOT_COUNTED, found, above)
        return found, jnp.where(at_most > ranks + 1, found, above)


def device_thresholds(
    X_dev: jax.Array, mask: jax.Array, max_bins: int = MAX_BINS
) -> np.ndarray:
    """Per-feature quantile thresholds of a matrix that is on the
    device, float32, shape ``(features, max_bins - 1)``: the linearly
    interpolated quantiles ``np.linspace(0, 1, max_bins + 1)[1:-1]`` of
    each column's finite values in the rows ``mask`` marks, equal to
    what ``np.nanquantile`` gives on those float32 values in float64
    and rounds to float32 (zeros of either sign come out as ``+0.0``).
    NaNs and infinities are left out (at assignment ``-inf`` lands in
    the first bin, ``+inf`` and NaN in the last); a column with no
    finite value gets all ``+inf``.

    Two small programs and two small copies back: the counts, from
    which the host takes numpy's own virtual indexes in float64, then
    the two order statistics round each index, which the host
    interpolates as numpy does. Exact: nothing is sampled or sketched.
    """
    quantiles = np.linspace(0, 1, max_bins + 1)[1:-1]
    counts = fetch(_finite_counts(X_dev, mask)).astype(np.int64)[:, None]
    last = np.maximum(counts, 1) - 1
    # numpy's `linear` method to the letter: the virtual index
    # (n - 1) * q, its floor and the next value up; where the index
    # reaches the last value numpy takes that twice, as index -1, and
    # its gamma is measured from -1 as well
    virtual = last * quantiles
    at_end = virtual >= last
    lower = np.where(at_end, -1, np.floor(virtual))
    gamma = virtual - lower
    ranks = np.where(at_end, last, lower).astype(np.int32)
    below, above = (
        _key_values(fetch(keys)).astype(np.float64)
        for keys in _bin_order_statistics(X_dev, mask, ranks)
    )
    step = above - below
    thresholds = below + step * gamma
    np.subtract(above, step * (1 - gamma), out=thresholds, where=gamma >= 0.5)
    thresholds[counts[:, 0] == 0] = np.inf
    return thresholds.astype(np.float32)


def _key_values(keys: np.ndarray) -> np.ndarray:
    """The float32 values of sortable keys."""
    return np.where(keys >= _SIGN, keys ^ _SIGN, ~keys).view(np.float32)


def distinct_thresholds(thresholds: np.ndarray) -> int:
    """Over the columns, how many distinct finite thresholds a
    ``(features, max_bins - 1)`` table holds: a column's quantiles rise
    along its row, so a threshold counts where it differs from the one
    before. ``features x (max_bins - 1)`` on continuous columns, one or
    two a column on 0/1 indicators: with it a column's values reach at
    most that many bins and one more."""
    new = np.ones(thresholds.shape, bool)
    new[:, 1:] = thresholds[:, 1:] != thresholds[:, :-1]
    return int((new & np.isfinite(thresholds)).sum())


def make_thresholds(X: np.ndarray, max_bins: int = MAX_BINS) -> np.ndarray:
    """Per-feature quantile thresholds of a host matrix, float32, shape
    ``(features, max_bins - 1)``: :func:`device_thresholds` of its
    float32 copy on the default device.

    Duplicate quantiles (constant-ish features) are harmless: empty bins
    simply never win a split. The quantiles are those of the values the
    device bins, i.e. of ``X`` as float32: for a float64 input whose
    values float32 does not hold they lie within one float32 ulp of the
    float64 quantiles.
    """
    X_dev = jnp.asarray(np.asarray(X), jnp.float32)
    return device_thresholds(X_dev, jnp.ones(X_dev.shape[0], bool), max_bins)


# Thresholds a fit has made, for the other fits of the same build: keyed
# by the host matrix's identity, dropped when the matrix is collected.
_shared: dict[tuple, Future] = {}
_shared_lock = threading.Lock()


def shared_thresholds(
    X, X_dev: jax.Array, mask: jax.Array, max_bins: int, mesh
) -> tuple[np.ndarray, int]:
    """``(thresholds, passes)`` for a fit whose host matrix ``X`` is on
    the device as ``(X_dev, mask)``. The first fit to ask for a given
    host array, ``max_bins`` and mesh runs :func:`device_thresholds`
    (``passes`` 1); every other one, while that array lives, waits for
    and takes the same result (``passes`` 0), or the first one's error,
    after which nothing is kept. An input that is no numpy array is
    not shared. The array is taken to hold the same values for as long
    as it is the same object.
    """
    if not isinstance(X, np.ndarray):
        return device_thresholds(X_dev, mask, max_bins), 1
    key = (id(X), max_bins, mesh)
    with _shared_lock:
        result = _shared.get(key)
        first = result is None
        if first:
            result = _shared[key] = Future()
            weakref.finalize(X, _shared.pop, key, None)
    if not first:
        return result.result(), 0
    try:
        thresholds = device_thresholds(X_dev, mask, max_bins)
        thresholds.flags.writeable = False
    except BaseException as error:
        with _shared_lock:
            _shared.pop(key, None)
        result.set_exception(error)
        raise
    result.set_result(thresholds)
    return thresholds, 1


@jax.jit
def apply_bins(X: jax.Array, thresholds: jax.Array) -> jax.Array:
    """Assign each value its bin index in ``[0, max_bins)``, on device:
    the bin is the count of its feature's thresholds below the value,
    ``#{k : thresholds[f, k] < X[r, f]}``, with NaN sent to the last
    bin — the integer ``np.searchsorted(thresholds[f], X[:, f],
    side="left")`` gives for sorted thresholds, ties and repeats
    included.

    Counted, not searched: ``max_bins - 1`` compares a value, each a
    dense elementwise op with the threshold broadcast along the rows,
    and XLA fuses the whole sum into one pass that reads the float32
    matrix once and writes the bins once (no temporary of the matrix's
    size). A binary search takes fewer steps, but every step is a
    data-dependent gather per value, which the TPU's vector unit
    serialises.

    int8 result (when the bin count fits): the binned matrix is the
    tree fits' largest long-lived buffer after the float32 matrix, a
    quarter of its bytes. Index arithmetic downstream promotes to int32
    as needed.
    """
    num_thresholds = thresholds.shape[1]
    with jax.named_scope("lo.bin"):
        bins = sum(
            (thresholds[:, k] < X).astype(jnp.int32)
            for k in range(num_thresholds)
        )
        bins = jnp.where(jnp.isnan(X), num_thresholds, bins)
    max_bins = num_thresholds + 1
    return bins.astype(jnp.int8 if max_bins <= 127 else jnp.int32)
