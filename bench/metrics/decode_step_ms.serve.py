"""Device time of the paged decode-chunk program in the traced window, over
the decode steps it ran (executions x decode_chunk)."""
from bench import readings

KIND, UNIT = "per_layer", "ms"


def read(records, red):
    if red is None or not readings.serve_records(records):
        return None
    s, n = readings.module_time(red, readings.DECODE_MODULE)
    return 1e3 * s / (n * records["decode_chunk"]) if n else None
