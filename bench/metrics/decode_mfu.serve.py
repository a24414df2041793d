"""Model FLOPs of the decode tokens delivered in the traced window (the
architecture's ``decode_token_flops``, ``bench/models/``: for llama,
bench/flops.py's projections, MLP, attention over each token's KV length and
unembedding), over the device time of the decode-chunk program, over the
chip's peak. Slots that were not live do no useful work and count nothing.
An architecture that gives no count reads nothing."""
from bench import models, peaks, readings

KIND, UNIT = "per_layer", "%"


def read(records, red):
    if red is None or not readings.serve_records(records):
        return None
    cfg = records["cfg"]
    count = getattr(models.of(cfg), "decode_token_flops", None)
    kv = readings.decode_tokens_traced(records)
    s, n = readings.module_time(red, readings.DECODE_MODULE)
    if count is None or not kv or not s:
        return None
    work = sum(count(cfg, k) for k in kv)
    return 100.0 * work / s / peaks.peaks(records["device_kind"]).flops
