"""A run of a K-class configuration that no cell lists, on the CPU:
the test-only configuration ``data/multiclass.json`` with its label's
classes set, rows from this file's own small maker, one fault planted
in the program or none; then the harness's ``main`` with
``--rehearsal-rows``, the result on the last line of standard output.

    python3 multiclass_driver.py <fault> <classes> <rows> <folder>

``folder`` receives the grown ``BENCHMARK.json`` and the configuration.

:func:`make` is the maker: non-negative columns of scales from 0.3 to
30 (naive Bayes takes no negatives, and the reference's logistic
regression works on the raw columns), the label the top of a linear
score of the first ``informative`` columns plus Gumbel noise.
"""

import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import numpy as np

import fault_driver
from lib import cells

WORKLOAD = "multiclass-test.build"
SEED = 2147483871


def configuration(classes: int) -> dict:
    config = cells.read_json(os.path.join(HERE, "data", "multiclass.json"))
    config["label"] = {"classes": classes}
    config["dataset"] = dict(config["dataset"], classes=classes, columns=config["features"])
    return config


def make(dataset: dict, seed: int, rows: int):
    """``rows`` rows as ``(columns, labels, field_names)``."""
    rng = np.random.default_rng([int(seed), int(dataset["salt"])])
    width, classes = int(dataset["columns"]), int(dataset["classes"])
    scale = np.exp(rng.uniform(np.log(0.3), np.log(30.0), size=width))
    X = rng.gamma(2.0, 1.0, size=(rows, width)) * scale
    effect = np.zeros((width, classes))
    effect[: int(dataset["informative"])] = rng.normal(size=(int(dataset["informative"]), classes))
    # centred and of unit spread, so that no class is rare
    signal = (X / scale - 2.0) @ effect / np.sqrt(2.0 * int(dataset["informative"]))
    score = signal + float(dataset["label_noise"]) * rng.gumbel(size=(rows, classes))
    labels = score.argmax(axis=1).astype(np.int64)
    columns = [X[:, j].astype(np.float32) for j in range(width)]
    return columns, labels, [f"x{j}" for j in range(width)]


def grown_root(folder: str, classes: int) -> str:
    """A checkout's root under ``folder`` whose ``BENCHMARK.json`` adds
    the test-only configuration and a cell of it to the committed ones."""
    os.makedirs(folder, exist_ok=True)
    spec = cells.read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    path = os.path.join(folder, "multiclass-test.json")
    with open(path, "w") as handle:
        json.dump(configuration(classes), handle)
    spec["configs"].append({
        "name": "multiclass-test", "source": "test-only", "file": path,
        "reduced": [], "why": "test-only",
    })
    spec["workloads"].append({
        "name": WORKLOAD, "config": "multiclass-test", "traffic": "build-loop",
        "chips": 1, "why": "test-only",
    })
    with open(os.path.join(folder, "BENCHMARK.json"), "w") as handle:
        json.dump(spec, handle)
    return folder


def binary():
    """Every fit sees the labels folded to 0 and 1 or more."""
    from learningorchestra_tpu.ml import base, builder

    make_classifier = builder.make_classifier

    class Folded:
        def __init__(self, inner):
            self.inner = inner

        def fit(self, X, y):
            folded = np.minimum(np.asarray(y), 1)
            return self.inner.fit(base.TrainingInput(X.X, folded, X.mesh), folded)

    builder.make_classifier = lambda name, mesh=None: Folded(make_classifier(name, mesh=mesh))


def swapped():
    """The stored probabilities of classes 1 and 2 exchanged where the
    build hands its predictions to the store."""
    from learningorchestra_tpu.core.columns import Column
    from learningorchestra_tpu.ml import builder

    columns_of = builder._prediction_columns

    def exchanged(*args):
        columns = columns_of(*args)
        proba = np.array(columns["probability"].data[: columns["probability"].size])
        proba[:, [1, 2]] = proba[:, [2, 1]]
        columns["probability"] = Column.from_numpy(proba)
        return columns

    builder._prediction_columns = exchanged


FAULTS = dict(fault_driver.FAULTS, binary=binary, swapped=swapped)


if __name__ == "__main__":
    fault, classes, rows, folder = sys.argv[1:5]
    root = grown_root(folder, int(classes))
    cells.Cell = functools.partial(cells.Cell, root=root)
    load_module = cells.load_module
    cells.load_module = lambda kind, name, *rest: (
        sys.modules[__name__] if (kind, name) == ("datasets", "multiclass_test")
        else load_module(kind, name, *rest)
    )
    FAULTS[fault]()
    import run as bench

    code = bench.main([
        "--workload", WORKLOAD, "--seed", str(SEED), "--seconds", "1",
        "--trace", "0", "--rehearsal-rows", rows,
    ])
    sys.stdout.flush()
    os._exit(code)
