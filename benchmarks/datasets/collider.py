"""Seeded stand-in for a collider-physics table (UCI HIGGS / HEPMASS).

No network here, so the rows are made from the seed with the source's
shape: row counts, 28 float32 features, class balance and split come
from the configuration; the value distributions and the label rule are
this file's and are listed under ``assumed`` there.

Columns, in order (``dataset.columns`` in the configuration gives the
counts): ``momentum`` heavy-tailed positive (log-normal, mean about 1,
like the normalised transverse momenta), ``angle`` symmetric (a clipped
normal or a uniform, shifted to be non-negative because MLlib's
multinomial naive Bayes refuses negative values), ``tag`` few-valued
(three levels, like the b-tag columns), ``mass`` derived positive
columns that depend on the earlier ones and on the class (like the
invariant masses). The label is a noisy non-linear function of several
columns, cut at the quantile that gives the configured positive share,
so tree ensembles beat the linear model and nothing reaches 1.0.

Every column has its own child seed, so threads change the speed and
never the values.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

TAG_LEVELS = np.array([0.0, 1.0865, 2.1731], dtype=np.float32)


def _column(kind: str, index: int, rows: int, seed_seq) -> np.ndarray:
    rng = np.random.default_rng(seed_seq)
    if kind == "momentum":
        z = rng.standard_normal(rows, dtype=np.float32)
        return np.exp(np.float32(0.55) * z - np.float32(0.15))
    if kind == "angle":
        if index % 2:  # azimuth-like: uniform over one turn
            return rng.random(rows, dtype=np.float32) * np.float32(6.2832)
        z = rng.standard_normal(rows, dtype=np.float32)
        return np.clip(z, -2.5, 2.5) + np.float32(2.5)
    if kind == "tag":
        level = rng.choice(3, size=rows, p=[0.5, 0.3, 0.2])
        return TAG_LEVELS[level]
    raise ValueError(f"unknown column kind {kind!r}")


def make(dataset: dict, seed: int, rows: int) -> tuple[list[np.ndarray], np.ndarray, list[str]]:
    """``rows`` rows as ``(columns, labels, field_names)``: a list of
    float32 column vectors, int64 labels, and the field names."""
    counts = dataset["columns"]
    kinds = (
        ["momentum"] * counts["momentum"]
        + ["angle"] * counts["angle"]
        + ["tag"] * counts["tag"]
    )
    n_mass = counts["mass"]
    children = np.random.SeedSequence(
        [int(seed), int(dataset.get("salt", 0))]
    ).spawn(len(kinds) + n_mass + 1)
    with ThreadPoolExecutor(max_workers=8) as pool:
        base = list(
            pool.map(
                lambda item: _column(item[1], item[0], rows, children[item[0]]),
                enumerate(kinds),
            )
        )
    momentum = base[: counts["momentum"]]
    angle = base[counts["momentum"] : counts["momentum"] + counts["angle"]]
    tag = base[counts["momentum"] + counts["angle"] :]

    # the hidden score: interactions, an opening angle and the tags
    score = (
        np.float32(0.9) * np.log(momentum[0]) * np.log(momentum[1] + np.float32(0.5))
        + np.float32(0.7) * np.cos(angle[1] - angle[3])
        + np.float32(0.5) * np.abs(angle[0] - angle[2])
        - np.float32(0.6) * (momentum[2] > np.float32(1.2))
        + np.float32(0.4) * tag[0] * (tag[1] > 0)
        + np.float32(0.5) * np.sqrt(momentum[3] * momentum[4])
    )
    noise_rng = np.random.default_rng(children[-1])
    score = score + np.float32(dataset["label_noise"]) * noise_rng.logistic(
        size=rows
    ).astype(np.float32)
    cut = np.quantile(score, 1.0 - float(dataset["positive_share"]))
    labels = (score > cut).astype(np.int64)

    # derived, class-dependent positive columns (invariant-mass-like)
    mass = []
    for k in range(n_mass):
        rng = np.random.default_rng(children[len(kinds) + k])
        a = momentum[k % len(momentum)]
        b = momentum[(k + 3) % len(momentum)]
        spread = np.exp(
            np.float32(0.3) * rng.standard_normal(rows, dtype=np.float32)
        )
        shift = np.float32(1.0) + np.float32(0.08 * (k + 1)) * labels.astype(
            np.float32
        )
        mass.append(np.sqrt(a * b + np.float32(0.25)) * spread * shift)
    columns = base + mass
    fields = [f"f{i}" for i in range(len(columns))]
    return columns, labels, fields
