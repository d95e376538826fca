"""Seconds in which no operation ran on the chip while the program was
inside one of the spans named in ``spans``: the idle gaps of the traced
window (first chip) cut to the union of those spans' intervals, over
all the builds' job traces. Spans and gaps share the wall clock
(``xplane.summarise``). With ``other`` it is the complement: the idle
seconds inside **none** of them, idleness that no named host span
explains. No device trace, or (without ``other``) no such span in any
trace: nothing returned."""

from readers.xplane import _clip, _union, iter_spans


def read(run: dict, args: dict):
    trace = run.get("device_trace")
    if not trace:
        return None
    names = set(args["spans"])
    intervals = [
        (span["start_ts"], span["start_ts"] + (span.get("duration_s") or 0.0))
        for build in run["builds"]
        for root in (build.get("trace") or {}).get("spans", [])
        for span, _ in iter_spans(root)
        if span["name"] in names and span.get("start_ts") is not None
    ]
    if not intervals and not args.get("other"):
        return None
    covered = _union(intervals)
    inside = sum(
        b - a for lo, hi in trace["gaps"] for a, b in _clip(covered, lo, hi)
    )
    if args.get("other"):
        return sum(hi - lo for lo, hi in trace["gaps"]) - inside
    return inside
