"""``correct`` holds on sound tiny runs and comes out false when the timed
path is broken underneath (bench/faults.py), and the controls read above the
limits, on the CPU at tiny sizes."""
import pytest

from bench import common, faults
from bench.tests import cells


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return cells.bench_copy(tmp_path_factory.mktemp("bench"))


def _run(bench, cell, seed, tmp_path, fault=None):
    if fault is None:
        return cells.run_tiny(bench, cell, seed, tmp_path)
    with faults.FAULTS[fault]():
        return cells.run_tiny(bench, cell, seed, tmp_path)


def _failing(out):
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


def test_serve_sound_run_is_correct(bench, tmp_path):
    out = _run(bench, "tiny.serve", 21, tmp_path)
    assert out["correct"], out["checks"]


def test_serve_altered_token_is_caught(bench, tmp_path):
    out = _run(bench, "tiny.serve", 21, tmp_path, "token_altered")
    assert not out["correct"]
    assert "logit_gap" in _failing(out)


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch",
                                   "no_gossip"])
def test_bilevel_fault_is_caught(bench, tmp_path, fault):
    out = _run(bench, "tiny.bilevel", 22, tmp_path, fault)
    assert not out["correct"], out["checks"]


def test_bilevel_sound_run_is_correct(bench, tmp_path):
    out = _run(bench, "tiny.bilevel", 22, tmp_path)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["tiny.serve", "tiny.bilevel"])
def test_control_fails_a_limit(bench, tmp_path, cell):
    """The control (fp8 reference for serving, bf16 reference for the
    bilevel loop) in the program's place reads above a limit."""
    import time

    from bench import run
    c = common.load_json(bench / "workloads" / f"{cell}.json")
    c["name"] = cell
    cfg = common.load_config(bench, c["config"])
    drv = common.load_module(bench / "drivers" / f"{c['driver']}.py")
    run._use_cache(tmp_path / "cache")
    ctx = common.Context(t_start=time.perf_counter(), trace=False,
                         trace_dir=tmp_path / "trace")
    rec = drv.run(ctx, c, cfg, 23, 3.0)
    ctl = drv.control(c, cfg, 23, rec)
    assert any(ctl[k] > float(c["check"][k]) for k in ctl), ctl
