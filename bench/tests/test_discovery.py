"""A workload, a configuration and a metric added as new files are found by
name and run, on the CPU at a tiny size, with no edit to any harness file."""
import json

from bench.tests import cells

COUNT_METRIC = '''"""Tokens the client received in the run (a count, no chip needed)."""
KIND, UNIT = "end_to_end", "tokens"


def read(records, red):
    if records.get("kind") != "serve":
        return None
    return sum(len(r["tokens"]) for r in records["requests"])
'''


def test_new_files_are_found_and_run(tmp_path):
    bench = cells.bench_copy(tmp_path)
    (bench / "metrics" / "served_tokens.test.py").write_text(COUNT_METRIC)
    out = cells.run_tiny(bench, "tiny.serve", 7, tmp_path)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    # the new count is read; every time, rate or share needs the chip and
    # stays out of a CPU run's metrics
    assert set(out["metrics"]) == {"served_tokens.test"}
    served = out["metrics"]["served_tokens.test"]["value"]
    assert served > 0 and out["attempted"] == 18
    json.dumps(out)   # the result line is plain JSON


def test_trace_run_on_cpu_prints_no_device_metric(tmp_path):
    bench = cells.bench_copy(tmp_path)
    out = cells.run_tiny(bench, "tiny.bilevel", 8, tmp_path, trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"] == {}
    assert "busy_s" not in out["device"]
