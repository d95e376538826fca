"""Sharding helpers: rows over the ``data`` axis, replication, padding.

Replaces the reference's RDD partitioning (reference:
microservices/projection_image/projection.py:104-111 reads a Mongo
collection as Spark partitions). A table's row dimension is sharded over
the mesh's ``data`` axis with ``jax.device_put``; XLA then inserts ICI
collectives for any cross-shard reduction instead of a shuffle.

TPU note: row counts are padded to a multiple of the data-axis size
(static shapes — XLA compiles one program per padded shape, and
estimators carry an explicit validity mask rather than using dynamic
shapes). Padded counts are additionally BUCKETED to a quarter-octave
geometric grid (1/1.25/1.5/1.75 × powers of two) so nearby dataset
sizes share one padded shape: without the grid every distinct row count
recompiles every estimator program, which at 10M rows made XLA
compilation — not compute — the wall-clock (a 273 s NB "fit" around a
27 ms kernel, round 4). Worst-case padding waste is 25% of rows on
kernels that are memory-bound anyway; masks keep the math exact.
``LO_SHAPE_BUCKETS=0`` restores minimal padding.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.parallel.mesh import DATA_AXIS
from learningorchestra_tpu.telemetry import tracing as _tracing
from learningorchestra_tpu.utils.dtypepolicy import dtype_policy
from learningorchestra_tpu.utils.hostfill import TILE_BYTES, fill_row_tiles
from learningorchestra_tpu.utils.shapegrid import bucket_count, grid_size


def bucket_rows(n: int) -> int:
    """Smallest quarter-octave grid value >= n: {4,5,6,7} x 2^k.

    THE padded-shape grid, shared with the serving MicroBatcher and the
    job coalescer — one copy of the math (utils/shapegrid.py) so the
    padding paths cannot drift apart.
    """
    return bucket_count(n)


def padded_row_count(n: int, multiple: int) -> int:
    """Rows after bucket-then-align padding — THE padded-shape rule.

    Shared by :func:`pad_rows` and the per-host feeder
    (``multihost.shard_rows_local``) so single-host and per-host-fed
    arrays land on identical global shapes.
    """
    # grid_size honors LO_SHAPE_BUCKETS (read once in utils/shapegrid —
    # the one copy of both the math and the knob)
    target = grid_size(n)
    return ((target + multiple - 1) // multiple) * multiple


def _validity_mask(n: int, padded_n: int) -> np.ndarray:
    mask = np.zeros(padded_n, dtype=bool)
    mask[:n] = True
    return mask


def pad_rows(array: np.ndarray, multiple: int) -> tuple[np.ndarray, np.ndarray]:
    """Pad axis 0 to the bucketed grid; returns (padded, validity mask)."""
    n = array.shape[0]
    padded_n = padded_row_count(n, multiple)
    mask = _validity_mask(n, padded_n)
    if padded_n == n:
        return array, mask
    pad_width = [(0, padded_n - n)] + [(0, 0)] * (array.ndim - 1)
    return np.pad(array, pad_width), mask


def row_sharded(mesh: Mesh) -> NamedSharding:
    """Rows over ``data``, everything else replicated."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def policy_dtype(dtype):
    """The dtype a float buffer actually ships in under
    ``LO_DTYPE_POLICY``: ``bf16`` maps float requests to bfloat16 —
    halving the H2D transfer and the HBM-resident matrix — while int,
    bool, and mask buffers are never touched. Identity under ``f32``."""
    if dtype is None:
        return None
    if dtype_policy() == "bf16" and np.issubdtype(
        np.dtype(dtype), np.floating
    ):
        return jnp.bfloat16
    return dtype


def _stage(
    source: np.ndarray, padded_n: int, dtype
) -> tuple[np.ndarray, int, int]:
    """The array ``shard_rows`` ships: ``source``'s rows cast to
    ``dtype`` and zero rows up to ``padded_n``,
    written once into one fresh array, so no padded copy in the
    source's dtype is ever made. The rows go in tiles of about
    ``TILE_BYTES`` and, where there are many, on several threads
    (``fill_row_tiles``): a serve batch stays on this one. Returns
    ``(array, threads, tiles)``."""
    n = source.shape[0]
    out = np.empty((padded_n,) + source.shape[1:], dtype=dtype)
    row_bytes = max(source.itemsize, out.itemsize) * math.prod(out.shape[1:])
    tile_rows = max(1, TILE_BYTES // max(1, row_bytes))

    def fill_rows(first_row: int, last_row: int) -> None:
        for start in range(first_row, last_row, tile_rows):
            stop = min(last_row, start + tile_rows)
            # assignment casts as astype does ("unsafe"): the same bits
            out[start:min(stop, n)] = source[start:stop]
            out[max(start, n):stop] = 0

    threads, tiles = fill_row_tiles(padded_n, tile_rows, fill_rows)
    return out, threads, tiles


def shard_rows(
    array: np.ndarray, mesh: Mesh, dtype=None
) -> tuple[jax.Array, jax.Array]:
    """Pad + device_put an array row-sharded over the mesh.

    Returns ``(device_array, device_mask)`` where the boolean mask marks
    real (non-padding) rows; both are sharded identically so masked
    reductions stay local until the final psum. Float ``dtype`` requests
    flow through :func:`policy_dtype`, so ``LO_DTYPE_POLICY=bf16``
    halves every feature-matrix transfer at THE H2D funnel without any
    caller opting in per site. Where ``dtype`` is given the shipped
    array is always a fresh one (on the CPU backend ``device_put`` may
    alias a host buffer, and the caller's must never be handed over);
    where it is None and no row needs padding, ``array`` ships as-is.

    In the active trace the host's half is an ``h2d:stage`` span (the
    one write of the shipped array, padded in place: ``bytes``, the
    host bytes written, with the mask; ``threads`` and ``tiles``, how
    many filled it and the row tiles they wrote, 0 and 0 where nothing
    was) and the hand-off an ``h2d:put`` span, both under the caller's
    span. The put returns before the arrays land: it times the dispatch
    and the host copy jax makes, not the transfer, which only a caller
    that waits for the arrays can time (``ml/base.TrainingInput``).
    """
    from learningorchestra_tpu.telemetry import profile

    n_shards = mesh.shape[DATA_AXIS]
    with _tracing.span("h2d:stage"):
        source = np.asarray(array)
        n = source.shape[0]
        padded_n = padded_row_count(n, n_shards)
        mask = _validity_mask(n, padded_n)
        if dtype is None and padded_n == n:
            padded, threads, tiles = source, 0, 0
        else:
            padded, threads, tiles = _stage(
                source,
                padded_n,
                source.dtype if dtype is None else policy_dtype(dtype),
            )
        written = mask.nbytes + (0 if padded is source else padded.nbytes)
        _tracing.annotate(bytes=int(written), threads=threads, tiles=tiles)
    sharding = row_sharded(mesh)
    # Flight-recorder byte accounting at THE H2D funnel (every matrix/
    # label transfer in the product path comes through here): counts
    # into lo_h2d_bytes_total and the caller's span, outside the two
    # above. Host-side only — identical on every process, no
    # collective, SPMD-safe.
    profile.account_h2d(int(padded.nbytes) + int(mask.nbytes))
    with _tracing.span("h2d:put"):
        return (
            jax.device_put(padded, sharding),
            jax.device_put(mask, sharding),
        )


def put_replicated(value, mesh: Mesh) -> jax.Array:
    return jax.device_put(jnp.asarray(value), replicated(mesh))
