"""Share of its roofline the paged decode-attention kernel reaches in the
traced window: the least time, the larger of its FLOPs over peak FLOP/s and
the bytes it must read over HBM bandwidth (the architecture's
``attention_cost`` times its ``attention_calls`` per step, ``bench/models/``;
for llama the K/V bytes of bench/flops.py bound it), over the device time of
the operations named ``ATTENTION_KERNEL``. An architecture that gives none
of the three reads nothing."""
from bench import models, peaks, readings

KIND, UNIT = "per_layer", "%"


def read(records, red):
    if red is None or not readings.serve_records(records):
        return None
    cfg = records["cfg"]
    model = models.of(cfg)
    kernel = getattr(model, "ATTENTION_KERNEL", None)
    cost = getattr(model, "attention_cost", None)
    calls = getattr(model, "attention_calls", None)
    if kernel is None or cost is None or calls is None:
        return None
    kv = readings.decode_tokens_traced(records)
    t = readings.op_time(red, kernel)
    if not kv or not t:
        return None
    f, b = cost(cfg, kv)
    L = calls(cfg)
    pk = peaks.peaks(records["device_kind"])
    least = max(L * f / pk.flops, L * b / pk.hbm_bw)
    return 100.0 * least / t
