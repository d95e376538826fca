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


def _plant(array: np.ndarray) -> np.ndarray:
    """``array`` with the values a cast must carry bit for bit planted
    in its first cells: NaN, ±inf, −0.0, a float64 denormal (0 in
    float32) and a float32 denormal; for ints, values outside int32."""
    flat = array.reshape(-1)
    if array.dtype.kind == "f":
        flat[:6] = [np.nan, np.inf, -np.inf, -0.0, 5e-324, 1e-40]
    elif array.dtype.kind == "i":
        flat[:3] = [2**40 + 5, -(2**35) - 3, 2**31]
    return array


def _shard_and_keep(monkeypatch, array, dtype):
    """``shard_rows`` on the default mesh under a trace; returns the
    host arrays it handed ``device_put`` and its ``h2d:stage`` span."""
    from learningorchestra_tpu.telemetry import tracing

    handed = []
    device_put = jax.device_put

    def keep(value, sharding):
        handed.append(value)
        return device_put(value, sharding)

    monkeypatch.setattr(jax, "device_put", keep)
    trace = tracing.Trace()
    with tracing.activate(trace):
        shard_rows(array, default_mesh(), dtype=dtype)
    (stage,) = [s for s in trace.spans if s.name == "h2d:stage"]
    return handed, stage


class TestStage:
    """``shard_rows`` writes the array it ships once, padded in place:
    the same bits ``np.pad`` then ``astype`` gave, with no padded copy
    in the source's dtype, on threads where the array is large."""

    @pytest.mark.parametrize("policy", ["f32", "bf16"])
    @pytest.mark.parametrize("rows", [37, 40])  # 37 pads to 40; 40 does not
    @pytest.mark.parametrize(
        "source_dtype, shape, dtype",
        [
            (np.float64, (3,), np.float32),
            (np.float64, (), np.float32),
            (np.float64, (3,), None),
            (np.float32, (3,), np.float32),
            (np.int64, (), np.int32),
            (np.int64, (2,), np.int32),
            (np.bool_, (), None),
            (np.bool_, (3,), np.int32),
        ],
    )
    def test_shipped_bits_equal_pad_then_cast(
        self, monkeypatch, policy, rows, source_dtype, shape, dtype
    ):
        from learningorchestra_tpu.parallel import sharding
        from learningorchestra_tpu.utils import dtypepolicy

        monkeypatch.setattr(dtypepolicy, "_POLICY", [policy])
        rng = np.random.default_rng(rows)
        source = _plant(
            (rng.normal(size=(rows,) + shape) * 1e3).astype(source_dtype)
        )
        original = source.copy()
        (shipped, mask), stage = _shard_and_keep(monkeypatch, source, dtype)

        expected, expected_mask = pad_rows(source, 8)
        if dtype is not None:
            expected = expected.astype(sharding.policy_dtype(dtype))
        assert shipped.dtype == expected.dtype
        assert shipped.shape == expected.shape == (40,) + shape
        assert (
            np.ascontiguousarray(shipped).view(np.uint8).tobytes()
            == np.ascontiguousarray(expected).view(np.uint8).tobytes()
        )
        assert mask.dtype == bool
        np.testing.assert_array_equal(mask, expected_mask)
        assert (source.view(np.uint8) == original.view(np.uint8)).all()
        if dtype is None and rows == 40:
            assert shipped is source  # nothing to pad or cast: as-is
            assert stage.meta == {"bytes": 40, "threads": 0, "tiles": 0}
        else:
            assert not np.shares_memory(shipped, source)
            # the mask and the shipped array, and no float64 pad copy
            assert stage.meta == {
                "bytes": 40 + shipped.nbytes, "threads": 1, "tiles": 1
            }

    def test_a_large_array_is_written_by_threads(self, monkeypatch):
        from learningorchestra_tpu.utils.hostfill import FILL_THREADS

        rows = (1 << 20) - 5  # pads to 1,048,576: 16 tiles of 1 MiB
        source = _plant(np.random.default_rng(0).normal(size=(rows, 2)))
        (shipped, mask), stage = _shard_and_keep(
            monkeypatch, source, np.float32
        )
        assert stage.meta["threads"] == FILL_THREADS > 1
        assert stage.meta["tiles"] == 16
        assert stage.meta["bytes"] == (1 << 20) * (2 * 4 + 1)
        expected = np.pad(source, [(0, 5), (0, 0)]).astype(np.float32)
        assert shipped.view(np.uint32).tobytes() == expected.view(
            np.uint32
        ).tobytes()
        np.testing.assert_array_equal(mask, pad_rows(source, 8)[1])

    def test_a_serve_batch_stays_on_one_thread(self, monkeypatch):
        source = np.random.default_rng(0).normal(size=(64, 28))
        (shipped, _), stage = _shard_and_keep(monkeypatch, source, np.float32)
        assert stage.meta == {
            "bytes": 64 * 28 * 4 + 64, "threads": 1, "tiles": 1
        }
        np.testing.assert_array_equal(shipped, source.astype(np.float32))


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
