"""Share of its roofline the paged decode-attention kernel reaches in the
traced window: the least time, the larger of its FLOPs over peak FLOP/s and
the K/V bytes it must read over HBM bandwidth (bench/flops.py; the bytes
bound it), over the device time of the kernel's operations."""
from bench import flops, peaks, readings

KIND, UNIT = "per_layer", "%"


def read(records, red):
    if red is None or not readings.serve_records(records):
        return None
    kv = readings.decode_tokens_traced(records)
    t = readings.op_time(red, readings.PAGED_KERNEL)
    if not kv or not t:
        return None
    cfg = records["cfg"]
    f, b = flops.paged_attention_cost(cfg, kv)
    L = cfg["num_hidden_layers"]
    pk = peaks.peaks(records["device_kind"])
    least = max(L * f / pk.flops, L * b / pk.hbm_bw)
    return 100.0 * least / t
