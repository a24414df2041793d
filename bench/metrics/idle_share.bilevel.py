"""Share of the traced window in which no operation ran on the chip:
1 - (union of device operation intervals) / window."""
KIND, UNIT = "per_layer", "%"


def read(records, red):
    if red is None or records.get("kind") != "bilevel":
        return None
    return 100.0 * red["idle_share"]
