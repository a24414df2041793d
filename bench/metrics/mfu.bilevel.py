"""Logistic-regression MDBO step FLOPs from shapes (bench/flops.py) times the
steps run in the traced window, over the window, over the chip's peak."""
from bench import flops, peaks

KIND, UNIT = "per_layer", "%"


def read(records, red):
    if red is None or records.get("kind") != "bilevel" or not records.get(
            "traced_steps"):
        return None
    s = records["shapes"]
    work = flops.logreg_step_flops(nodes=s["K"], batch=s["batch"], d=s["d"],
                                   c=s["c"], J=s["J"])
    pk = peaks.peaks(records["device_kind"]).flops
    return 100.0 * work * records["traced_steps"] / red["window_s"] / pk
