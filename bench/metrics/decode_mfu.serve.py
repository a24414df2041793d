"""Model FLOPs of the decode tokens delivered in the traced window
(bench/flops.py: projections, MLP, attention over each token's KV length,
unembedding), over the device time of the decode-chunk program, over the
chip's peak. Slots that were not live do no useful work and count nothing."""
from bench import flops, peaks, readings

KIND, UNIT = "per_layer", "%"


def read(records, red):
    if red is None or not readings.serve_records(records):
        return None
    kv = readings.decode_tokens_traced(records)
    s, n = readings.module_time(red, readings.DECODE_MODULE)
    if not kv or not s:
        return None
    work = sum(flops.decode_token_flops(records["cfg"], k) for k in kv)
    return 100.0 * work / s / peaks.peaks(records["device_kind"]).flops
