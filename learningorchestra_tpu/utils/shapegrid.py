"""The padded-shape grid: quarter-octave size bucketing, shared.

XLA compiles one program per shape, so every padding path in this
codebase rounds a varying count up to a small geometric grid instead of
compiling one program per exact size:

- ``parallel/sharding.py`` buckets dataset ROW counts before aligning
  them to the mesh's data axis (without it, every distinct row count
  recompiled every estimator — round 4's 273 s NB "fit" around a 27 ms
  kernel).
- ``serve/batcher.py`` pads micro-batched predict requests to a fixed
  ``LO_SERVE_MAX_BATCH`` floor so all small traffic shares ONE compiled
  forward per model.
- ``sched/coalesce.py`` pads the JOB axis of a fused vmap-across-jobs
  dispatch, so coalesced batch sizes share compiled programs instead of
  causing a compile storm.

This module is the one copy of that math (two private copies is how the
paths drift). The floor semantics double as a reproducibility guarantee
the coalescer leans on: two dispatches padded to the SAME grid value run
the SAME XLA program, and a vmap slice's result depends only on its own
inputs — so a job fused into a batch of N and the same job run alone
produce bit-identical results whenever both land on one grid value.

Stdlib + numpy only; safe to import from the scheduler, the store
server, and the serving lane without pulling in jax.
"""

from __future__ import annotations

import os

import numpy as np

# LO_SHAPE_BUCKETS=0 restores minimal padding everywhere the grid is
# consulted (rows, micro-batches above their floor, coalesced job
# axes). Read once: per-request reads could desynchronize padded shapes
# — and so dispatch counts — across the hosts of a multi-host mesh.
# lo: allow[LO305] module-level read-once by design (see above)
_BUCKETS_ENABLED = os.environ.get("LO_SHAPE_BUCKETS", "1") != "0"


def bucket_count(n: int) -> int:
    """Smallest quarter-octave grid value >= n: {4,5,6,7} x 2^k.

    Every value is a multiple of a power of two at least n/8, so grid
    values compose cleanly with mesh-size multiples of 2/4/8 devices.
    Values <= 8 pass through (the grid would be sub-integer there, and
    tiny shapes compile fast). Idempotent: grid values map to
    themselves, so bucketing an already-bucketed count never grows it.
    """
    if n <= 8:
        return n
    power = 1 << (n.bit_length() - 1)  # largest power of two <= n
    if n == power:
        return n
    for quarters in (5, 6, 7, 8):
        candidate = power * quarters // 4
        if candidate >= n:
            return candidate
    raise AssertionError("unreachable: 2*power >= n by construction")


def grid_size(n: int, floor: int = 0) -> int:
    """``n`` rounded up to the padded-shape grid, with a fixed floor.

    Counts at or under ``floor`` pad to exactly ``floor`` (the
    MicroBatcher's fixed-dispatch-shape trick: all small traffic shares
    ONE compiled program); larger counts ride the quarter-octave grid,
    which bounds the number of distinct compiled shapes logarithmically.
    ``LO_SHAPE_BUCKETS=0`` disables the above-floor bucketing (the
    debug knob for shape-dependent issues) — the floor itself stays,
    as it did before the grid was shared.
    """
    if n <= floor:
        return floor
    return bucket_count(n) if _BUCKETS_ENABLED else n


def pad_axis0(array: np.ndarray, target: int) -> np.ndarray:
    """Zero-pad ``array`` along axis 0 up to ``target`` rows (no copy
    when already there). Callers carry their own validity discipline —
    a mask, or slicing the pad back off after the dispatch."""
    n = array.shape[0]
    if n >= target:
        return array
    pad_width = [(0, target - n)] + [(0, 0)] * (array.ndim - 1)
    return np.pad(array, pad_width)


def padded_indices(n: int, target: int) -> list[int]:
    """Source indices for padding a stacked axis to ``target`` entries
    by REPLICATING entry 0 into the dummy slots: ``[0..n-1, 0, 0, ...]``.

    Replication (not zeros) keeps dummy vmap slices numerically inert —
    an all-zero dummy member would divide by a zero mask-sum and drag
    NaNs through the fused program's dummy lanes; a replica computes a
    discarded copy of real work instead.
    """
    if n < 1:
        raise ValueError("padded_indices needs at least one real entry")
    return list(range(n)) + [0] * (target - n)
