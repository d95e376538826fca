"""Seconds inside the program's own spans, from the job traces of the
window's builds: the durations of every span named ``span`` (under an
ancestor named ``under``, where given), summed within a build and
averaged over the builds. Host clock; a ``phase:fit`` ends in
``block_until_ready``."""

from readers.xplane import iter_spans


def read(run: dict, args: dict):
    totals = []
    for build in run["builds"]:
        trace = build.get("trace")
        if not trace:
            continue
        total, found = 0.0, False
        for root in trace["spans"]:
            for span, path in iter_spans(root):
                if span["name"] != args["span"]:
                    continue
                if args.get("under") and args["under"] not in path:
                    continue
                total += span.get("duration_s") or 0.0
                found = True
        if found:
            totals.append(total)
    return sum(totals) / len(totals) if totals else None
