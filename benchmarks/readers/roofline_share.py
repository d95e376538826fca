"""A tree-fit or binning program's share of its memory roofline, in per cent: the
bytes one build cannot avoid moving through that program
(``bytes_model``), over the chip's peak HBM bandwidth from
``peaks.json``, over the program's device seconds in the capture. It is
memory-bound: the work is a histogram over int8 bins. No device time,
no share."""

from readers import bytes_model
from readers.xla_module_seconds import seconds


def read(run: dict, args: dict):
    device_s = seconds(run, args["function"])
    if not device_s:
        return None
    config = run["config"]
    rows = int(config["rows"]["train"])
    features = int(config["features"])
    hyper = config["hyper"]
    depth = int(hyper["max_depth"])
    if args["kind"] == "binning":
        moved = bytes_model.binning_bytes(rows, features)
    elif args["kind"] == "boosting":
        moved = bytes_model.boosting_bytes(rows, features, depth, int(hyper["gbt_rounds"]))
    else:
        trees = int(hyper[args["trees"]]) if "trees" in args else 1
        side = trees if args.get("side_by_side_all") else 1
        calls = trees / side
        moved = calls * bytes_model.tree_fit_bytes(rows, features, depth, side)
    floor_s = moved / float(run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / device_s
