"""Mean seconds of one admission prefill, from ServeEngine.stats
(prefill_s / prefills) over every drain of the window; prefill_s is timed
on the host around a prefill that blocks on its first token."""
from bench import readings

KIND, UNIT = "per_layer", "ms"


def read(records, red):
    if not readings.serve_records(records):
        return None
    e = records["engine"]
    return 1e3 * e["prefill_s"] / e["prefills"] if e["prefills"] else None
