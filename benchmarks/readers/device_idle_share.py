"""The share of the traced window, in per cent, in which no operation
ran on the chip: 1 - busy / window from the profiler capture."""


def read(run: dict, args: dict):
    trace = run.get("device_trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
