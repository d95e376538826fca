"""Drives the rest of a run with the timed path broken underneath: plants
one fault in the program, then calls the harness's ``main`` with
``--rehearsal-rows`` (which is how the look for a chip is skipped) and
leaves the result on the last line of standard output.

    python3 fault_driver.py <fault> <workload> <rows>
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import numpy as np


def half_batch():
    """Half of the rows left out of every fit, the rest fitted as if
    they were all."""
    from learningorchestra_tpu.ml import builder

    make = builder.make_classifier

    class Halved:
        def __init__(self, inner):
            self.inner = inner

        def fit(self, X, y):
            return self.inner.fit(X[: len(X) // 2], y[: len(y) // 2])

    builder.make_classifier = lambda name, mesh=None: Halved(make(name, mesh=mesh))


def answer_altered():
    """One stored probability in a thousand altered where the build
    hands its predictions to the store."""
    from learningorchestra_tpu.core.columns import Column
    from learningorchestra_tpu.ml import builder

    columns_of = builder._prediction_columns

    def altered(frame):
        columns = columns_of(frame)
        proba = np.array(columns["probability"].data[: columns["probability"].size])
        proba[::1000] = proba[::1000] * 0.9 + 0.05
        columns["probability"] = Column.from_numpy(proba)
        return columns

    builder._prediction_columns = altered


def builds_after(real, then):
    """The first ``real`` calls of the service's ``build_model`` (the
    warm-up's is the first) go through; every later one does ``then``
    in its place."""
    from learningorchestra_tpu.services import model_builder

    build = model_builder.build_model
    calls = []

    def counted(*args, **kwargs):
        calls.append(1)
        return build(*args, **kwargs) if len(calls) <= real else then()

    model_builder.build_model = counted


def refuse():
    raise RuntimeError("planted: this build fails")


def poor_splits():
    """Every node of every tree split on its first feature's median:
    valid trees, honest leaves, and most of the gain left on the table."""
    import jax.numpy as jnp

    from learningorchestra_tpu.ml import trees

    def fixed(gain, subset_key, subset_k):
        nodes = gain.shape[0]
        return jnp.zeros(nodes, jnp.int32), jnp.full(nodes, gain.shape[2] // 2, jnp.int32)

    trees._select_splits = fixed


FAULTS = {
    "none": lambda: None,
    "half_batch": half_batch,
    "answer_altered": answer_altered,
    # every build of the window answers 201 and leaves the store and the
    # checkpoints as they were
    "state_unchanged": lambda: builds_after(1, lambda: None),
    # the same of every build but the window's first: what is found after
    # the window is sound, and is not the last build's
    "later_builds_unchanged": lambda: builds_after(2, lambda: None),
    # the window's second build answers 500
    "second_build_fails": lambda: builds_after(2, refuse),
    "poor_splits": poor_splits,
    # the program's own path in the precision below the stated float32
    "bf16": lambda: os.environ.__setitem__("LO_DTYPE_POLICY", "bf16"),
}



if __name__ == "__main__":
    fault, workload, rows = sys.argv[1:4]
    FAULTS[fault]()
    import run as bench

    code = bench.main([
        "--workload", workload, "--seed", "2147483777", "--seconds", "1",
        "--trace", "0", "--rehearsal-rows", rows,
    ])
    sys.stdout.flush()
    os._exit(code)
