"""Engine steps completed in the window, over the window (host clock): the
window runs from the run's on_eval call at t=0 to its last one."""
KIND, UNIT = "end_to_end", "steps/s"


def read(records, red):
    if records.get("kind") != "bilevel" or records.get("platform") != "tpu":
        return None
    return records["steps"] / records["window_s"]
