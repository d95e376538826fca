"""Feature binning: quantile thresholds + on-device bin assignment.

Trees on TPU want histogram-binned features: exact split search over raw
floats is data-dependent control flow, but binned split search is a dense
scatter/cumsum program with static shapes. Same trick Spark MLlib itself
uses (``maxBins=32`` default) and the reason its trees scale; here the
binning keeps every tree op on the MXU/VPU.

Bin semantics: ``bin b`` holds values ``thresholds[b-1] < x <=
thresholds[b]``; a split "at bin b" sends ``x <= thresholds[b]`` left, so
raw-feature prediction only needs the float threshold, never the bins.
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
    and land in the last bin at assignment (searchsorted sends NaN right),
    a one-sided missing-value policy like LightGBM's default.
    """
    quantiles = np.linspace(0, 1, max_bins + 1)[1:-1]
    with np.errstate(all="ignore"):
        thresholds = np.nanquantile(np.asarray(X, np.float64), quantiles, axis=0).T
    return np.nan_to_num(thresholds, nan=np.inf)


@jax.jit
def apply_bins(X: jax.Array, thresholds: jax.Array) -> jax.Array:
    """Assign each value its bin index in ``[0, max_bins)``: one
    vmapped ``searchsorted`` per feature, on device.

    int8 result (when the bin count fits): the binned matrix is the
    tree fits' largest long-lived buffer, and TPU tiling pads the
    feature-minor dimension to the 128-lane boundary — at 10M×16 an
    int32 binned matrix occupies ~5 GB of HBM after padding, int8 ~1.3
    GB. Index arithmetic downstream promotes to int32 as needed.
    """

    def one_feature(column, feature_thresholds):
        return jnp.searchsorted(feature_thresholds, column, side="left")

    with jax.named_scope("lo.bin"):
        bins = jax.vmap(one_feature, in_axes=(1, 0), out_axes=1)(X, thresholds)
    max_bins = thresholds.shape[1] + 1
    return bins.astype(jnp.int8 if max_bins <= 127 else jnp.int32)
