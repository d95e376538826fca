"""A numeric attribute of the program's own spans, from the job traces
of the window's builds: ``attr`` of every span named ``span`` (or whose
name starts with ``prefix``; under an ancestor named ``under``, where
given), summed within a build and averaged over the builds. Bytes a
transfer span carried, iterations a fit ran, the scheduler's own queue
wait. A span without the attribute is not a reading: no span with it,
nothing returned."""

from readers.xplane import iter_spans


def matching(trace: dict, args: dict):
    """``(span, path)`` for the spans of one job trace that ``span`` /
    ``prefix`` / ``under`` select."""
    for root in trace["spans"]:
        for span, path in iter_spans(root):
            if "span" in args and span["name"] != args["span"]:
                continue
            if "prefix" in args and not span["name"].startswith(args["prefix"]):
                continue
            if args.get("under") and args["under"] not in path[:-1]:
                continue
            yield span, path


def read(run: dict, args: dict):
    totals = []
    for build in run["builds"]:
        trace = build.get("trace")
        if not trace:
            continue
        values = [
            (span.get("meta") or {}).get(args["attr"])
            for span, _ in matching(trace, args)
        ]
        values = [v for v in values if isinstance(v, (int, float))]
        if values:
            totals.append(float(sum(values)))
    return sum(totals) / len(totals) if totals else None
