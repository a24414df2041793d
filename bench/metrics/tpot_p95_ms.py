"""95th percentile, over every request due in the window, of its time per
output token after the first: (last token - first token) / (n - 1), on the
host clock. Tokens reach the client in decode-chunk groups, so a gap between
two tokens is not defined; a request's mean still carries every stall it
lived through. An unfinished request counts to the end of the run."""
from bench import readings

KIND, UNIT = "end_to_end", "ms"


def read(records, red):
    if not readings.serve_records(records):
        return None
    end = records["end_s"]
    tpot = []
    for r in records["requests"]:
        if r["first_s"] is None or r["max_new"] < 2:
            continue
        done = len(r["tokens"]) >= r["max_new"]
        last = r["last_s"] if done else end
        tpot.append((last - r["first_s"]) / (r["max_new"] - 1))
    return readings.percentile_ms(tpot, 95) if tpot else None
