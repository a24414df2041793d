"""Device-idle time by span: on hand-made event lists, and on the small
trace recorded on the chip (fixtures/small.xplane.pb)."""
import pathlib

import pytest

from bench import span_idle, trace_reduce

MS = 1_000_000   # ns


def test_nesting_gives_idle_to_the_innermost_span():
    # engine.eval [10, 40] holds engine.on_eval [20, 30]; the device is idle
    # over [0, 50] but for [12, 18]
    spans = [("engine.eval", 10 * MS, 40 * MS),
             ("engine.on_eval", 20 * MS, 30 * MS)]
    busy = [[12 * MS, 18 * MS]]
    got = span_idle.idle_by_span(busy, (0, 50 * MS), spans)
    assert got == {"none": pytest.approx(0.020),          # [0,10] + [40,50]
                   "engine.eval": pytest.approx(0.014),   # 30 less 10 less 6
                   "engine.on_eval": pytest.approx(0.010)}
    assert sum(got.values()) == pytest.approx(0.050 - 0.006)


def test_a_gap_that_straddles_two_spans_is_split():
    # idle [10, 30] runs from serve.emit [5, 20] into serve.admit [20, 40];
    # the device is busy over the rest of [0, 40]
    spans = [("serve.emit", 5 * MS, 20 * MS), ("serve.admit", 20 * MS, 40 * MS),
             ("serve.prefill", 32 * MS, 38 * MS)]
    busy = [[0, 10 * MS], [30 * MS, 40 * MS]]
    got = span_idle.idle_by_span(busy, (0, 40 * MS), spans)
    assert got["serve.emit"] == pytest.approx(0.010)
    assert got["serve.admit"] == pytest.approx(0.010)
    assert got["serve.prefill"] == 0.0     # the device was busy under it
    assert got["none"] == 0.0


def test_spans_are_clipped_to_the_window_and_ties_go_to_the_shorter():
    spans = [("outer", -5 * MS, 15 * MS), ("inner", 0, 5 * MS)]
    got = span_idle.idle_by_span([], (0, 10 * MS), spans)
    assert got == {"inner": pytest.approx(0.005),
                   "outer": pytest.approx(0.005)}
    # two spans opened at once: the one that closes first is inside
    spans = [("outer", 0, 10 * MS), ("inner", 0, 4 * MS)]
    got = span_idle.idle_by_span([], (0, 10 * MS), spans)
    assert got == {"inner": pytest.approx(0.004),
                   "outer": pytest.approx(0.006)}


def test_innermost_pieces_tile_the_window():
    spans = [("a", 2, 8), ("b", 4, 6), ("c", 6, 12)]
    pieces = span_idle.innermost(spans, (0, 10))
    assert pieces == [("none", 0, 2), ("a", 2, 4), ("b", 4, 6),
                      ("c", 6, 10)]


def test_base_name_drops_traceme_args():
    assert span_idle.base_name("serve.prefill#rid=5,prompt_len=9#") == (
        "serve.prefill")
    assert span_idle.base_name("engine.eval") == "engine.eval"


FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "small.xplane.pb"


def test_recorded_chip_trace_idle_adds_up():
    """Every idle nanosecond of the window goes to one name: the names'
    sum is the window less the busy time trace_reduce finds."""
    red = trace_reduce.reduce(FIXTURE, window="bench.traced",
                              annotations=("bench.submit", "bench.wait"))
    got = span_idle.reduce(FIXTURE, window="bench.traced",
                           names=("bench.submit", "bench.wait"))
    assert got["window_s"] == pytest.approx(red["window_s"], rel=1e-9)
    assert sum(got["idle_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    # the host slept 20 ms three times under bench.wait with the chip idle
    assert got["count"]["bench.wait"] >= 3
    assert got["idle_s"]["bench.wait"] > 3 * 0.019
