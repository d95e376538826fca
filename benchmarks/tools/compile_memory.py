#!/usr/bin/env python3
"""Reckon a cell's device memory without a chip: compile the build's
largest jitted programs at the configuration's padded shapes for a
*described* v5e (``jax.experimental.topologies``) and print
``memory_analysis()``. Compile-time figures, never chip runs: nothing
executes, and one program is counted at a time, not what else the
process keeps on the device.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_memory.py higgs-11m [dt gb rf]

A build's peak is about its live buffers - the float32 train matrix
(rows x features x 4 B), up to three int8 binned copies (rows x
features), labels, weights and margins (4 B a row each), the test
matrix - plus the largest program's temporaries: programs run one after
another on one chip, so temporaries do not add up. The driver wants
25 % of a chip's memory (4 GiB) used at the peak.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding


def main(config_name: str, programs: list[str]) -> None:
    from learningorchestra_tpu.ml import trees
    from learningorchestra_tpu.parallel.sharding import padded_row_count

    config = json.load(open(os.path.join(BENCH_DIR, "configs", f"{config_name}.json")))
    hyper = config["hyper"]
    features = config["features"]
    rows = padded_row_count(config["rows"]["train"], 1)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    bins = shape((rows, features), jnp.int8)
    y = shape((rows,), jnp.int32)
    weights = shape((rows,), jnp.float32)
    static = dict(max_depth=hyper["max_depth"], max_bins=hyper["max_bins"])
    lowered = {
        "dt": lambda: trees._dt_fit.lower(bins, y, weights, num_classes=2, **static),
        "gb": lambda: trees._gbt_rounds.lower(
            bins, y, weights, shape((rows,), jnp.float32), rounds=1,
            step=shape((), jnp.float32), **static),
        "rf": lambda: trees._rf_chunk.lower(
            bins, y, weights, jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 1)),
            num_classes=2, subset_k=6, **static),
    }
    print(f"{config_name}: {config['rows']['train']} train rows pad to {rows}; "
          f"float32 matrix {rows * features * 4 / 2**30:.2f} GiB, "
          f"int8 bins {rows * features / 2**30:.2f} GiB")
    for name in programs:
        started = time.monotonic()
        analysis = lowered[name]().compile().memory_analysis()
        print(
            f"  {name}: arguments {analysis.argument_size_in_bytes / 2**30:.2f} GiB, "
            f"temporaries {analysis.temp_size_in_bytes / 2**30:.2f} GiB, "
            f"outputs {analysis.output_size_in_bytes / 2**30:.3f} GiB "
            f"(compiled in {time.monotonic() - started:.0f} s)"
        )


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:] or ["dt", "gb", "rf"])
