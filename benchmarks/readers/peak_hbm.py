"""Peak bytes in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GiB."""


def read(run: dict, args: dict):
    peak = run.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
