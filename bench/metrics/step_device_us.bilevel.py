"""Device busy time in the traced window over the Engine steps run in it."""
KIND, UNIT = "per_layer", "us"


def read(records, red):
    if red is None or records.get("kind") != "bilevel" or not records.get(
            "traced_steps"):
        return None
    return 1e6 * red["busy_s"] / records["traced_steps"]
