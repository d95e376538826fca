"""From a profiler capture (``*.xplane.pb``) to the few numbers the
per-layer metrics read. Kept with the benchmark so that every PR
computes them the same way; checked in ``benchmarks/tests`` against a
small recorded capture.

A TPU capture holds one plane per chip (``/device:TPU:<n>``) whose line
``XLA Modules`` has one event per execution of a jitted program (named
``jit_<function>(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per operation inside it. Busy time is the union of the operations'
intervals (of the modules' where a plane has no operations line), cut to
the window; a module's seconds are the sum of its executions.

Clocks: events are in nanoseconds from the capture's start. The harness
writes one ``TraceAnnotation`` named ``bench_window`` whose ``wall``
stat is the wall clock at that instant, which puts the program's spans
(wall clock) and the device's events on one axis.
"""

from __future__ import annotations

import glob
import os
import re

MARKER = "bench_window"
MIN_GAP_S = 1e-5
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_capture(directory: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def module_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def reduce(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """``{"wall_at_zero", "devices": {plane: {"busy": [(s, e)], "modules":
    {name: [(s, e)]}}}}``, times in seconds from the capture's start."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    wall_at_zero = None
    devices: dict = {}
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            modules: dict[str, list] = {}
            op_intervals: list = []
            module_intervals: list = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for event in line.events:
                        start = event.start_ns / 1e9
                        span = (start, start + event.duration_ns / 1e9)
                        modules.setdefault(module_name(event.name), []).append(span)
                        module_intervals.append(span)
                elif line.name == "XLA Ops":
                    for event in line.events:
                        start = event.start_ns / 1e9
                        op_intervals.append((start, start + event.duration_ns / 1e9))
            devices[plane.name] = {
                "busy": _union(op_intervals or module_intervals),
                "modules": modules,
            }
        elif wall_at_zero is None and plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    if event.name == MARKER:
                        stats = dict(event.stats)
                        if "wall" in stats:
                            wall_at_zero = float(stats["wall"]) - event.start_ns / 1e9
                            break
                if wall_at_zero is not None:
                    break
    return {"wall_at_zero": wall_at_zero, "devices": devices}


def summarise(reduced: dict, start_wall: float, end_wall: float) -> dict:
    """Busy seconds (mean over the chips), per-module seconds (summed
    over the chips, divided by their number) and the idle gaps of the
    first chip, all cut to the window ``[start_wall, end_wall]``."""
    zero = reduced["wall_at_zero"]
    if zero is None:
        raise ValueError("the capture has no bench_window marker")
    lo, hi = start_wall - zero, end_wall - zero
    devices = reduced["devices"]
    if not devices:
        raise ValueError("the capture has no device plane")
    busy_each = []
    modules: dict[str, dict] = {}
    for plane in devices.values():
        busy = _clip(plane["busy"], lo, hi)
        busy_each.append(sum(b - a for a, b in busy))
        for name, spans in plane["modules"].items():
            cut = _clip(spans, lo, hi)
            if cut:
                entry = modules.setdefault(name, {"seconds": 0.0, "count": 0})
                entry["seconds"] += sum(b - a for a, b in cut) / len(devices)
                entry["count"] += len(cut)
    first = devices[sorted(devices)[0]]
    busy = _clip(first["busy"], lo, hi)
    gaps = []
    cursor = lo
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor + zero, a + zero))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor + zero, hi + zero))
    # the pauses between one program's own operations are not idle gaps
    gaps = [g for g in gaps if g[1] - g[0] >= MIN_GAP_S]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_each) / len(busy_each),
        "modules": modules,
        "gaps": sorted(gaps, key=lambda g: g[0] - g[1]),
    }


def iter_spans(span: dict, path: tuple = ()):
    here = path + (span["name"],)
    yield span, here
    for child in span.get("children", []):
        yield from iter_spans(child, here)


def name_gap(gap: tuple[float, float], traces: list[dict]) -> str:
    """What the program was doing at the middle of an idle gap: the
    deepest program spans that cover it, ``train:<clf>`` kept in the
    name of a phase, or "no span"."""
    middle = (gap[0] + gap[1]) / 2
    names = set()
    for trace in traces:
        for root in trace.get("spans", []):
            for span, path in iter_spans(root):
                start = span.get("start_ts")
                if start is None or not start <= middle <= start + (span.get("duration_s") or 0):
                    continue
                covered_child = any(
                    c.get("start_ts") is not None
                    and c["start_ts"] <= middle <= c["start_ts"] + (c.get("duration_s") or 0)
                    for c in span.get("children", [])
                )
                if covered_child or span["name"].startswith("job:"):
                    continue
                owner = next((p for p in path if p.startswith("train:")), "")
                label = span["name"]
                if owner and owner != label:
                    label = f"{label}[{owner[6:]}]"
                names.add(label)
    return "+".join(sorted(names)[:4]) if names else "no span"
