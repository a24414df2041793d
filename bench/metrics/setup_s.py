"""Seconds from process start to the window's start: imports, weights and
data made on the device from the seed, compiling or reading the compile
cache, and warm-up (host clock)."""
KIND, UNIT = "end_to_end", "s"


def read(records, red):
    if records.get("platform") != "tpu":
        return None
    return records["setup_s"]
