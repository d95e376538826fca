"""Device seconds of one jitted program in the harness's own profiler
capture: the executions of the XLA module ``jit_<function>`` inside the
window, summed, averaged over the chips and divided by the window's
builds. Nothing ran, nothing returned."""


def seconds(run: dict, function: str):
    trace = run.get("device_trace")
    if not trace:
        return None
    entry = trace["modules"].get(f"jit_{function}")
    builds = sum(1 for b in run["builds"] if b["status"] == 201)
    if not entry or not builds:
        return None
    return entry["seconds"] / builds


def read(run: dict, args: dict):
    return seconds(run, args["function"])
