"""95th percentile, over every request due in the window, of the time from
when it was due to when its first token reached the client (host clock). A
request that never produced a token counts with the time it waited."""
from bench import readings

KIND, UNIT = "end_to_end", "ms"


def read(records, red):
    if not readings.serve_records(records):
        return None
    end = records["end_s"]
    ttft = [(r["first_s"] if r["first_s"] is not None else end) - r["due_s"]
            for r in records["requests"]]
    return readings.percentile_ms(ttft, 95)
