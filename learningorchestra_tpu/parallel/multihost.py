"""Multi-host runtime: coordinator bootstrap, per-host feeding, gather.

The reference scales by adding Spark workers to a master/worker overlay
(reference: docker-compose.yml:123-163; README.md:94 ``docker service
scale microservice_sparkworker=3``). The TPU equivalent is a multi-host
slice: every host runs the SAME program, ``jax.distributed`` wires the
hosts into one runtime, ``jax.devices()`` returns the global device
list, and the existing ``(data, model)`` mesh simply spans hosts — XLA
routes data-axis collectives over ICI within a host and DCN across
hosts. No worker protocol is written here; the sharding annotations are
the protocol.

Three pieces:

- :func:`initialize_from_env` — process bootstrap from ``LO_COORDINATOR``
  / ``LO_NUM_PROCESSES`` / ``LO_PROCESS_ID`` (the deployment knob; on
  Cloud TPU the args can be omitted and jax autodetects).
- :func:`host_row_range` / :func:`shard_rows_local` — per-host feeding:
  each host loads ONLY its row slice and
  ``jax.make_array_from_process_local_data`` assembles the global array
  without any host ever materializing the full dataset (the 100M-row
  ingestion story; the reference instead relies on every Spark worker
  reading its partitions from Mongo).
- :func:`fetch` — host-side view of results: replicated or
  single-host arrays come back with ``np.asarray``; row-sharded
  multi-host arrays are ``process_allgather``-ed so every host sees the
  same global result (the ``collect()`` analogue).

Single-process runs hit none of this machinery: ``fetch`` degrades to
``np.asarray`` and ``shard_rows_local`` to a plain ``device_put``.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.parallel.mesh import DATA_AXIS

_ENV_COORDINATOR = "LO_COORDINATOR"
_ENV_NUM_PROCESSES = "LO_NUM_PROCESSES"
_ENV_PROCESS_ID = "LO_PROCESS_ID"


def initialize_from_env() -> bool:
    """Join the multi-host runtime if the environment asks for one.

    Reads ``LO_COORDINATOR`` (host:port), ``LO_NUM_PROCESSES`` and
    ``LO_PROCESS_ID``; when all are present, calls
    ``jax.distributed.initialize`` so this process's devices join the
    global runtime. Idempotent; returns True when running multi-host.

    On CPU (the virtual-mesh test rig) cross-process collectives need
    the gloo transport, which must be configured before the backend
    initializes — done here, gated to the CPU platform.
    """
    if jax.distributed.is_initialized():
        return jax.process_count() > 1
    coordinator = os.environ.get(_ENV_COORDINATOR)
    num_processes = os.environ.get(_ENV_NUM_PROCESSES)
    process_id = os.environ.get(_ENV_PROCESS_ID)
    if not (coordinator and num_processes and process_id):
        return False
    if jax.config.jax_platforms and "cpu" in jax.config.jax_platforms:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )
    return jax.process_count() > 1


def _local_data_coords(mesh: Mesh) -> list[int]:
    """Sorted data-axis coordinates owned by this process, verified
    contiguous (guaranteed when the mesh is built from ``jax.devices()``
    order, parallel/mesh.py)."""
    data_axis_index = mesh.axis_names.index(DATA_AXIS)
    coords = sorted(
        {
            idx[data_axis_index]
            for idx, dev in np.ndenumerate(mesh.devices)
            if dev.process_index == jax.process_index()
        }
    )
    if coords and coords != list(range(coords[0], coords[-1] + 1)):
        raise ValueError(
            "this host's data-axis coordinates are not contiguous; "
            "build the mesh from jax.devices() order"
        )
    return coords


def host_row_range(n_rows: int, mesh: Mesh) -> tuple[int, int]:
    """Global row range this host must feed for an ``n_rows`` dataset
    row-sharded over ``mesh``'s data axis.

    Rows are dealt in contiguous blocks along the data axis, so every
    host owns one contiguous slice of the (padded) row space. The stop
    is clamped to ``n_rows``; padding rows are synthesized by
    :func:`shard_rows_local`, never loaded.
    """
    from learningorchestra_tpu.parallel.sharding import padded_row_count

    data_size = mesh.shape[DATA_AXIS]
    # padded rows per data-axis coord — the bucketed rule, so per-host
    # feeding matches sharding.pad_rows's global shapes exactly
    block = padded_row_count(n_rows, data_size) // data_size
    coords = _local_data_coords(mesh)
    if not coords:
        return 0, 0
    return min(coords[0] * block, n_rows), min((coords[-1] + 1) * block, n_rows)


def shard_rows_local(
    local_rows: np.ndarray,
    mesh: Mesh,
    n_rows: int,
    dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Assemble a global row-sharded array from this host's slice.

    ``local_rows`` must be exactly the ``host_row_range(n_rows, mesh)``
    slice of the global dataset. Rows are padded (per host) up to the
    block boundary and returned with the matching global validity mask,
    mirroring ``sharding.shard_rows``'s contract — the two are
    interchangeable from the estimators' point of view.
    """
    from learningorchestra_tpu.parallel.sharding import padded_row_count

    local_rows = np.asarray(local_rows)
    if dtype is not None:
        local_rows = local_rows.astype(dtype)
    data_size = mesh.shape[DATA_AXIS]
    block = padded_row_count(n_rows, data_size) // data_size
    padded_n = block * data_size
    start, stop = host_row_range(n_rows, mesh)
    if len(local_rows) != stop - start:
        raise ValueError(
            f"expected rows [{start}, {stop}) = {stop - start} rows, "
            f"got {len(local_rows)}"
        )
    # Pad this host's slice out to its share of the padded row space.
    local_padded_n = len(_local_data_coords(mesh)) * block
    pad = local_padded_n - len(local_rows)
    local_mask = np.zeros(local_padded_n, dtype=bool)
    local_mask[: len(local_rows)] = True
    if pad:
        pad_width = [(0, pad)] + [(0, 0)] * (local_rows.ndim - 1)
        local_rows = np.pad(local_rows, pad_width)
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    global_shape = (padded_n,) + local_rows.shape[1:]
    arr = jax.make_array_from_process_local_data(
        sharding, local_rows, global_shape=global_shape
    )
    mask = jax.make_array_from_process_local_data(
        sharding, local_mask, global_shape=(padded_n,)
    )
    return arr, mask


def fetch(arr: jax.Array) -> np.ndarray:
    """Host numpy view of a device array, multi-host safe.

    Fully-addressable arrays (single process, or replicated outputs)
    convert directly; row-sharded arrays spanning hosts are gathered
    with ``process_allgather`` so every host returns the same global
    value — the TPU-native ``collect()``.
    """
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
