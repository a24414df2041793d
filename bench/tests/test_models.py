"""Each serving configuration's weights, reference and counts come from
``bench/models/<model_type>.py``: a second architecture runs as new files
only, the readers that need a count read nothing where its module has
none, and read for llama what they read before the lookup existed."""
import pathlib
import time

import pytest

from bench import common, trace_reduce
from bench.tests import cells

ALT_MODULE = pathlib.Path(__file__).parent / "fixtures" / "tiny_alt.py"
MS = 1_000_000   # ns


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    bench = cells.bench_copy(tmp_path_factory.mktemp("bench"))
    cells.add_model(bench, "tiny_alt", cells.TINY_ALT, ALT_MODULE)
    return bench


def test_second_architecture_runs_as_new_files(bench, tmp_path):
    out = cells.run_tiny(bench, "tiny-alt.serve", 31, tmp_path, trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 18 and out["failed"] == 0
    # the paged kernel's own count needs no chip; the metrics that need a
    # count the module lacks, or the chip, read nothing
    assert set(out["metrics"]) == {"paged_pages_per_step.serve"}
    pages = out["metrics"]["paged_pages_per_step.serve"]
    assert pages["unit"] == "pages" and pages["value"] >= 1.0


def fabricated(cfg):
    """Records of a traced serving run on the chip and its reduction, made
    by hand: two requests, four token deliveries of which two fall in the
    traced window, a decode-chunk program and a paged kernel op."""
    records = {
        "kind": "serve", "platform": "tpu", "device_kind": "TPU v5 lite",
        "cfg": cfg, "decode_chunk": 8, "trace_window": (1.0, 2.0),
        "requests": [{"prompt_len": 100}, {"prompt_len": 300}],
        # (host time, request, first index, count)
        "deltas": [(0.5, 0, 0, 1), (1.2, 0, 1, 8), (1.5, 1, 0, 8),
                   (2.5, 1, 8, 8)],
    }
    ops = [("%paged_attention.5 = bf16[32,5,3,64] custom-call(...)",
            1 * MS, 4 * MS),
           ("%fusion.2 = f32[8] fusion(...)", 4 * MS, 11 * MS)]
    mods = [("jit_chunk(1)", 1 * MS, 11 * MS),
            ("jit_prefill(2)", 12 * MS, 14 * MS)]
    per = trace_reduce.reduce_events(ops, mods, (0, 20 * MS), [])
    return records, trace_reduce.combine([per], ["/device:TPU:0"])


# read by the metric files of the commit before bench/models/ existed, on
# fabricated(smollm-360m); the lookup must leave them as they were
GOLDEN_LLAMA = {"decode_mfu.serve": 0.5693897421319797,
                "paged_attn_roofline.serve": 5.016224664224665}


@pytest.mark.parametrize("metric", sorted(GOLDEN_LLAMA))
@pytest.mark.parametrize("config", ["smollm-360m", "tiny-alt"])
def test_counted_readers_by_model_type(bench, config, metric):
    records, red = fabricated(common.load_config(bench, config))
    value = common.load_module(bench / "metrics" / f"{metric}.py").read(
        records, red)
    assert value == (GOLDEN_LLAMA[metric] if config == "smollm-360m"
                     else None)


def test_unknown_model_type_fails_before_the_window(bench, tmp_path):
    name = cells.add_model(bench, "nope", dict(cells.TINY_ALT, name="nope",
                                               model_type="nope"))
    cell = common.load_json(bench / "workloads" / f"{name}.json")
    cell["name"] = name
    driver = common.load_module(bench / "drivers" / "serve_open_loop.py")
    ctx = common.Context(t_start=time.perf_counter(), trace=False,
                         trace_dir=tmp_path / "trace")
    with pytest.raises(FileNotFoundError, match="bench/models/nope.py"):
        driver.run(ctx, cell, common.load_config(bench, "nope"), 1, 3.0)
    assert ctx.t_open is None
