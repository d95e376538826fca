"""A counter family of ``/metrics``: its growth over each build of the
window (``over: build``, averaged), over the window (``over: window``),
or its value when set-up ended (``over: ready``). A count, so 0 is a
reading."""


def read(run: dict, args: dict):
    family = args["family"]
    over = args.get("over", "window")
    if over == "build":
        deltas = [
            b["counters_after"].get(family, 0.0) - b["counters_before"].get(family, 0.0)
            for b in run["builds"]
            if "counters_after" in b
        ]
        return sum(deltas) / len(deltas) if deltas else None
    counters = run["counters"]
    if over == "ready":
        return counters["ready"].get(family)
    if family not in counters["window_end"]:
        return None
    return counters["window_end"][family] - counters["window_start"].get(family, 0.0)
