"""Seconds a span spent in none of its children: its duration minus the
union of its children's intervals (cut to its own), for every span named
``span`` (under an ancestor named ``under``, where given), summed within
a build and averaged over the builds. What is left of a ``phase:fit``
once its host passes, transfers, compiles and device wait are taken
out: host work that no span names yet."""

from readers.span_attr import matching
from readers.xplane import _clip, _union


def self_seconds(span: dict) -> float:
    start = span.get("start_ts")
    duration = span.get("duration_s") or 0.0
    if start is None:
        return duration
    children = [
        (c["start_ts"], c["start_ts"] + (c.get("duration_s") or 0.0))
        for c in span.get("children", [])
        if c.get("start_ts") is not None
    ]
    covered = _union(_clip(children, start, start + duration))
    return duration - sum(b - a for a, b in covered)


def read(run: dict, args: dict):
    totals = []
    for build in run["builds"]:
        trace = build.get("trace")
        if not trace:
            continue
        own = [self_seconds(span) for span, _ in matching(trace, args)]
        if own:
            totals.append(sum(own))
    return sum(totals) / len(totals) if totals else None
