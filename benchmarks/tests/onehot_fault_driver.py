"""``fault_driver.py`` with the one-hot cell's own fault: a fit that
loses every feature block of its histograms but the first, at this
cell's block of 28 columns (the months and the first sixteen days).
Same use and same result line.

    python3 onehot_fault_driver.py <fault> <workload> <rows>
"""

import os
import sys

import fault_driver

BLOCK = 28


def first_block_only():
    """Every fit sees the first block of 28 columns only: the other
    columns' histograms read zero, so no node can split there."""
    import jax.numpy as jnp

    from learningorchestra_tpu.ml import trees

    whole = trees._level_histograms

    def first(bins, node, channels, n_nodes, max_bins):
        hist = whole(bins, node, channels, n_nodes, max_bins)
        seen = jnp.arange(hist.shape[1]) < BLOCK
        return jnp.where(seen[None, :, None, None], hist, 0.0)

    trees._level_histograms = first


FAULTS = dict(fault_driver.FAULTS, first_block_only=first_block_only)


if __name__ == "__main__":
    fault, workload, rows = sys.argv[1:4]
    FAULTS[fault]()
    import run as bench

    code = bench.main([
        "--workload", workload, "--seed", "2147483999", "--seconds", "1",
        "--trace", "0", "--rehearsal-rows", rows,
    ])
    sys.stdout.flush()
    os._exit(code)
