"""Seeded stand-in for a wide, dense, already-normalised table (PASCAL
Large Scale Learning Challenge 2008, *epsilon*: 2,000 real features).

No network here, so the rows are made from the seed with the source's
shape: width, class balance and the published preprocessing (each
column standardised to zero mean and unit variance, then each row
scaled to unit length) are the source's; the value distributions and
the label rule are this file's and are listed under ``assumed`` in the
configuration.

Columns: a few dozen latent factors plus each column's own noise
(``factor_share`` of a column's variance comes from ``loadings`` of the
``factors``), so columns are correlated as a real feature table's are
and every column is continuous. After the two normalisations values are
of order ``1 / sqrt(features)``.

Label: a noisy score cut at the quantile that gives the positive share.
The score is linear in ``informative_per_block`` columns of **every**
run of ``block`` columns (weights 0.3 to 1.5, drawn from the seed) plus
``interactions`` products of two such columns from different blocks, so
the columns that matter are spread over the whole width and a fit that
loses a block of columns leaves gain on the table; the logistic noise
keeps every classifier well under 1.0.

Every latent factor and every column has its own child seed, and the
row norms are summed in column order, so threads change the speed and
never the values.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8
ROW_BLOCK = 1 << 16


def informative_columns(dataset: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The columns the score is linear in, ``informative_per_block`` of
    every ``block``, and their weights."""
    width, block = int(dataset["features"]), int(dataset["block"])
    per_block = int(dataset["informative_per_block"])
    rng = np.random.default_rng([int(seed), int(dataset.get("salt", 0)), 1])
    columns = np.concatenate([
        start + rng.choice(min(block, width - start), size=per_block, replace=False)
        for start in range(0, width, block)
    ])
    weights = (0.3 + 1.2 * rng.random(len(columns)) ** 2) * rng.choice([-1.0, 1.0], len(columns))
    return np.sort(columns), weights.astype(np.float32)


def _over(function, items) -> list:
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return list(pool.map(function, items))


def make(dataset: dict, seed: int, rows: int) -> tuple[list[np.ndarray], np.ndarray, list[str]]:
    """``rows`` rows as ``(columns, labels, field_names)``: a list of
    float32 column vectors, int64 labels, and the field names."""
    width, factors = int(dataset["features"]), int(dataset["factors"])
    share = np.float32(dataset["factor_share"])
    children = np.random.SeedSequence(
        [int(seed), int(dataset.get("salt", 0))]
    ).spawn(factors + width + 1)

    latent = _over(
        lambda child: np.random.default_rng(child).standard_normal(rows, dtype=np.float32),
        children[:factors],
    )

    def column(j: int) -> np.ndarray:
        # in place, with one scratch vector: eight threads that each
        # allocate a megabyte an operation queue up in the allocator
        rng = np.random.default_rng(children[factors + j])
        values = rng.standard_normal(rows, dtype=np.float32)
        picked = rng.choice(factors, size=int(dataset["loadings"]), replace=False)
        loading = rng.standard_normal(len(picked)).astype(np.float32)
        loading *= np.sqrt(share / (loading**2).sum())
        scratch = np.empty(rows, dtype=np.float32)
        values *= np.sqrt(1 - share)
        for k, w in zip(picked, loading):
            values += np.multiply(latent[k], w, out=scratch)
        # the source's first step: zero mean and unit variance a column
        values -= np.float32(values.mean(dtype=np.float64))
        variance = np.square(values, out=scratch).mean(dtype=np.float64)
        values *= np.float32(1.0 / np.sqrt(variance))
        return values

    columns = _over(column, range(width))

    # the source's second step: every row scaled to unit length; summed
    # in column order within a row block, whatever the threads do
    def inverse_norm(start: int) -> np.ndarray:
        total = np.zeros(min(start + ROW_BLOCK, rows) - start, dtype=np.float64)
        for values in columns:
            part = values[start : start + ROW_BLOCK].astype(np.float64)
            total += part * part
        return (1.0 / np.sqrt(total)).astype(np.float32)

    scale = np.concatenate(_over(inverse_norm, range(0, rows, ROW_BLOCK)))

    def scaled(values: np.ndarray) -> np.ndarray:
        values *= scale
        return values

    columns = _over(scaled, columns)

    # the hidden score, on values brought back to order one
    informative, weights = informative_columns(dataset, seed)
    unit = np.float32(np.sqrt(width))
    score = np.zeros(rows, dtype=np.float32)
    for j, w in zip(informative, weights):
        score += w * unit * columns[j]
    rng = np.random.default_rng(children[-1])
    per_block = int(dataset["informative_per_block"])
    for _ in range(int(dataset["interactions"])):
        # two informative columns of two different blocks
        a, b = rng.choice(len(informative) // per_block, size=2, replace=False) * per_block
        score += np.float32(1.5) * (unit * columns[informative[a]]) * (unit * columns[informative[b]])
    score += np.float32(dataset["label_noise"]) * rng.logistic(size=rows).astype(np.float32)
    cut = np.quantile(score, 1.0 - float(dataset["positive_share"]))
    labels = (score > cut).astype(np.int64)
    fields = [f"f{j}" for j in range(width)]
    return columns, labels, fields
