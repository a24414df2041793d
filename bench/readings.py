"""Arithmetic that several metric files share: which tokens a traced window
produced, and which device time belongs to a named program or kernel."""
from __future__ import annotations

from bench import common

# the name the jitted decode chunk of the paged engine carries in the trace
# (see PERF.md, "Layers"); each architecture names its attention kernel in
# its own module (bench/models/)
DECODE_MODULE = "jit_chunk"


def on_chip(records: dict) -> bool:
    return records.get("platform") == "tpu"


def serve_records(records: dict) -> bool:
    return records.get("kind") == "serve" and on_chip(records)


def decode_tokens_traced(records: dict) -> list[int]:
    """KV length attended by each decode token delivered in the traced
    window (tokens after a request's first, which its prefill produced)."""
    w = records.get("trace_window")
    if not w:
        return []
    reqs = records["requests"]
    kv = []
    for t, i, first, count in records["deltas"]:
        if not (w[0] <= t <= w[1]):
            continue
        P = reqs[i]["prompt_len"]
        kv.extend(P + j for j in range(max(first, 1), first + count))
    return kv


def module_time(red: dict, prefix: str) -> tuple[float, int]:
    """(seconds, executions) of programs whose name starts with ``prefix``,
    mean over the traced devices."""
    devs = list(red["devices"].values())
    s = sum(v[0] for d in devs for k, v in d["modules"].items()
            if k.startswith(prefix)) / len(devs)
    n = sum(v[1] for d in devs for k, v in d["modules"].items()
            if k.startswith(prefix)) // len(devs)
    return s, n


def op_time(red: dict, part: str) -> float:
    """Device seconds of operations whose name holds ``part``, mean over
    the traced devices."""
    return sum(v for k, v in red["device_ops"] if part in k)


def percentile_ms(values, q) -> float:
    return 1e3 * common.percentile(values, q)
