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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MAX_BINS = 32


def make_thresholds(X: np.ndarray, max_bins: int = MAX_BINS) -> np.ndarray:
    """Per-feature quantile thresholds, shape ``(features, max_bins - 1)``.

    Duplicate quantiles (constant-ish features) are harmless: empty bins
    simply never win a split. NaNs are ignored when computing quantiles
    and land in the last bin at assignment (``apply_bins`` sends NaN
    there), a one-sided missing-value policy like LightGBM's default.
    """
    quantiles = np.linspace(0, 1, max_bins + 1)[1:-1]
    with np.errstate(all="ignore"):
        thresholds = np.nanquantile(np.asarray(X, np.float64), quantiles, axis=0).T
    return np.nan_to_num(thresholds, nan=np.inf)


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
