"""A number the harness took itself with the host's clock: ``key`` of
the run's ``harness`` record (set-up's parts)."""


def read(run: dict, args: dict):
    return run["harness"].get(args["key"])
