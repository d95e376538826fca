"""Seconds from the client's ``POST`` to the start of the first span
named ``span`` in the build's job trace (queueing, validation and the
scheduler's hand-off), averaged over the window's builds."""

from readers.xplane import iter_spans


def read(run: dict, args: dict):
    waits = []
    for build in run["builds"]:
        trace = build.get("trace")
        if not trace:
            continue
        starts = [
            span["start_ts"]
            for root in trace["spans"]
            for span, _ in iter_spans(root)
            if span["name"] == args["span"] and span.get("start_ts") is not None
        ]
        if starts:
            waits.append(min(starts) - build["start"])
    return sum(waits) / len(waits) if waits else None
