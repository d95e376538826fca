"""Seeded stand-in for a one-hot coded operational table: the public
GBDT comparisons' *Expo* (Data Expo 2009, airline on-time: 11,000,000
rows, the categorical columns one-hot coded to 700 columns, binary label
"departure delayed by 15 minutes or more").

No network here, so the rows are made from the seed with the source's
shape: the width, the six coded columns with their level counts, the two
numeric columns and the label's meaning are the source's; the level
shares, the effects and the noise are this file's and are listed under
``assumed`` in the configuration.

Columns, in this order: one 0/1 indicator a level of ``Month`` (12),
``DayofMonth`` (31), ``DayOfWeek`` (7), ``UniqueCarrier``
(``carriers``), ``Origin`` and ``Dest`` (``airports`` each) - exactly
one 1 a group a row - then ``DepTime`` (integer hhmm, 0-2359) and
``Distance`` (integer miles). All float32, none negative, none missing.

The seed draws a *world* first - the carriers' and airports' shares
(Zipf-like with an exponential tail, so the largest airport holds about
8 % of the rows and the smallest under a row in a million: some indicator
columns are all zero), where each airport lies, which destinations an
origin serves, and every effect of the label - and then the rows from
it. Another seed is another world, not the same rows in another order.

A day of the year gives ``Month`` and ``DayofMonth`` (so the 31st is
the rarest day), ``Dest`` is drawn given ``Origin`` (small airports fly
to hubs, nobody flies to where they are), and ``Distance`` is the
distance between the two plus a little noise.

Label: a logistic score - an effect a level of carrier, origin,
destination, month and day of week, a smooth effect of the departure
hour (the strongest: delays build up over the day), and two
interactions (carrier x band of the day, origin x month) - plus logistic
noise, cut at the quantile that gives ``positive_share``.
"""

from __future__ import annotations

import numpy as np

DAYS_IN_MONTH = (31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31)
HOUR_BANDS = 4  # night, morning, afternoon, evening: six hours each
MIN_MILES, MAX_MILES = 11, 4962


def group_sizes(dataset: dict) -> list[tuple[str, int]]:
    """The coded columns and their level counts, in column order."""
    carriers, airports = int(dataset["carriers"]), int(dataset["airports"])
    return [
        ("Month", 12), ("DayofMonth", 31), ("DayOfWeek", 7),
        ("UniqueCarrier", carriers), ("Origin", airports), ("Dest", airports),
    ]


def field_names(dataset: dict) -> list[str]:
    fields = [
        f"{name}_{level}"
        for name, levels in group_sizes(dataset)
        for level in range(1, levels + 1)
    ]
    return fields + ["DepTime", "Distance"]


def _shares(rng, levels: int, head: float, decay: float, noise: float):
    """Level shares that fall with the rank as ``exp(-rank / decay) /
    (rank + head)``, jittered, in an order drawn from the seed."""
    rank = np.arange(levels)
    weight = (rank + head) ** -1.0 * np.exp(-rank / decay)
    weight *= np.exp(noise * rng.standard_normal(levels))
    return rng.permutation(weight / weight.sum())


def make_world(dataset: dict, rng) -> dict:
    """What the seed decides before any row: shares, places, effects."""
    carriers, airports = int(dataset["carriers"]), int(dataset["airports"])
    world = {
        # the calendar is near-uniform: a season and a weekday pattern
        "day": 1.0 + 0.08 * np.sin(
            2 * np.pi * (np.arange(365) / 365.0 + rng.random())
        ),
        "weekday": 1.0 + 0.06 * rng.standard_normal(7),
        "carrier": _shares(rng, carriers, 1.5, carriers, 0.25),
        "airport": _shares(
            rng, airports, float(dataset["airport_head"]), float(dataset["airport_decay"]), 0.4
        ),
    }
    # where the airports lie, in miles; a few far out over the sea
    place = rng.random((airports, 2)) * (2600.0, 1400.0)
    place[rng.random(airports) < 0.03] += (2400.0, 900.0)
    world["miles"] = np.clip(
        np.hypot(*(place[:, None, :] - place[None, :, :]).transpose(2, 0, 1)),
        MIN_MILES, MAX_MILES,
    )
    # an origin's destinations: by the destination's own share, hubs the
    # more the smaller the origin, and never the origin itself
    share = world["airport"]
    hub = share >= np.sort(share)[-max(1, airports // 10)]
    pull = np.where(hub[None, :], 1.0 + 0.02 / share[:, None], 1.0)
    route = share[None, :] * pull * np.exp(0.5 * rng.standard_normal((airports, airports)))
    np.fill_diagonal(route, 0.0)
    world["route"] = np.cumsum(route / route.sum(axis=1, keepdims=True), axis=1)
    # every carrier's departures lie a little earlier or later in the day
    world["carrier_hour"] = rng.normal(0.0, 1.0, carriers)
    scale = dataset["effects"]
    world["effects"] = {
        "carrier": rng.normal(0.0, scale["carrier"], carriers),
        "origin": rng.normal(0.0, scale["origin"], airports),
        "dest": rng.normal(0.0, scale["dest"], airports),
        "month": rng.normal(0.0, scale["month"], 12),
        "weekday": rng.normal(0.0, scale["weekday"], 7),
        "carrier_band": rng.normal(0.0, scale["carrier_band"], (carriers, HOUR_BANDS)),
        "origin_month": rng.normal(0.0, scale["origin_month"], (airports, 12)),
        # delays build up from the first departures to the evening
        "hour_peak": rng.uniform(17.5, 20.5),
        "hour": float(scale["hour"]),
    }
    return world


def _draw(rng, share: np.ndarray, rows: int) -> np.ndarray:
    return rng.choice(len(share), size=rows, p=share / share.sum())


def hour_effect(effects: dict, hour: np.ndarray) -> np.ndarray:
    """Smooth in the hour of the day: lowest at five in the morning,
    highest at ``hour_peak``, of amplitude ``hour`` either way."""
    phase = np.clip((hour - 5.0) / (effects["hour_peak"] - 5.0), 0.0, None)
    rise = np.sin(0.5 * np.pi * np.minimum(phase, 1.0)) ** 2
    fall = np.exp(-np.maximum(phase - 1.0, 0.0) * 2.0)
    return effects["hour"] * (2.0 * rise * fall - 1.0)


def make(dataset: dict, seed: int, rows: int) -> tuple[list[np.ndarray], np.ndarray, list[str]]:
    """``rows`` rows as ``(columns, labels, field_names)``: a list of
    float32 column vectors, int64 labels, and the field names."""
    world_seed, row_seed = np.random.SeedSequence(
        [int(seed), int(dataset.get("salt", 0))]
    ).spawn(2)
    world = make_world(dataset, np.random.default_rng(world_seed))
    effects = world["effects"]
    rng = np.random.default_rng(row_seed)

    day = _draw(rng, world["day"], rows)
    first_day = np.concatenate([[0], np.cumsum(DAYS_IN_MONTH)])
    month = np.searchsorted(first_day[1:], day, side="right")
    day_of_month = day - first_day[month]
    weekday = _draw(rng, world["weekday"], rows)
    carrier = _draw(rng, world["carrier"], rows)
    origin = _draw(rng, world["airport"], rows)
    # the destination given the origin: one uniform a row against the
    # origin's own cumulative shares
    uniform = rng.random(rows)
    dest = np.empty(rows, dtype=np.int64)
    order = np.argsort(origin, kind="stable")
    starts = np.searchsorted(origin[order], np.arange(len(world["airport"]) + 1))
    for airport in range(len(world["airport"])):
        picked = order[starts[airport] : starts[airport + 1]]
        dest[picked] = np.searchsorted(world["route"][airport], uniform[picked], side="right")
    np.minimum(dest, len(world["airport"]) - 1, out=dest)

    # departures between five in the morning and midnight, a few at night
    hour = 5.0 + 19.0 * rng.beta(1.6, 1.9, rows) - 0.8 * np.tanh(world["carrier_hour"][carrier])
    hour = np.where(rng.random(rows) < 0.012, 24.0 * rng.random(rows), hour)
    minutes = np.clip(np.floor(hour * 60.0), 0, 24 * 60 - 1).astype(np.int64)
    dep_time = (minutes // 60) * 100 + minutes % 60
    miles = world["miles"][origin, dest] * (1.0 + 0.01 * rng.standard_normal(rows))
    distance = np.clip(np.rint(miles), MIN_MILES, MAX_MILES)

    score = (
        effects["carrier"][carrier] + effects["origin"][origin] + effects["dest"][dest]
        + effects["month"][month] + effects["weekday"][weekday]
        + hour_effect(effects, minutes / 60.0)
        + effects["carrier_band"][carrier, minutes // (60 * 24 // HOUR_BANDS)]
        + effects["origin_month"][origin, month]
        + float(dataset["label_noise"]) * rng.logistic(size=rows)
    )
    cut = np.quantile(score, 1.0 - float(dataset["positive_share"]))
    labels = (score > cut).astype(np.int64)

    columns = [
        (codes == level).astype(np.float32)
        for codes, (_, levels) in zip(
            (month, day_of_month, weekday, carrier, origin, dest), group_sizes(dataset)
        )
        for level in range(levels)
    ]
    columns += [dep_time.astype(np.float32), distance.astype(np.float32)]
    return columns, labels, field_names(dataset)
