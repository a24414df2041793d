"""The trace reduction: on hand-made event lists, and on a small trace
recorded on the chip (fixtures/small.xplane.pb, see make_fixture.py)."""
import pathlib

import pytest

from bench import trace_reduce

MS = 1_000_000   # ns


def test_busy_idle_overlap_and_gaps_by_hand():
    ops = [("fusion.1", 0, 10 * MS), ("fusion.2", 5 * MS, 15 * MS),
           ("collective-permute.3", 20 * MS, 40 * MS),
           ("fusion.4", 30 * MS, 35 * MS),
           ("fusion.5", 60 * MS, 70 * MS)]          # outside the window
    mods = [("jit_chunk(1)", 0, 15 * MS), ("jit_chunk(1)", 20 * MS, 40 * MS)]
    spans = [("bench.wait", 12 * MS, 22 * MS),
             ("bench.stream_next", 0, 30 * MS)]
    r = trace_reduce.reduce_events(ops, mods, (0, 50 * MS), spans)
    assert r["window_s"] == pytest.approx(0.050)
    assert r["busy_s"] == pytest.approx(0.035)        # [0,15] + [20,40]
    assert r["idle_share"] == pytest.approx(0.3)
    assert r["collective_s"] == pytest.approx(0.020)
    assert r["collective_exposed_s"] == pytest.approx(0.015)   # minus [30,35]
    assert r["ops"]["fusion.2"] == pytest.approx(0.010)
    assert r["ops"]["collective-permute.3"] == pytest.approx(0.015)  # self
    assert "fusion.5" not in r["ops"]
    assert r["modules"]["jit_chunk(1)"] == (pytest.approx(0.035), 2)
    # gaps [15,20] (innermost open span: bench.wait) and [40,50] (none)
    assert r["idle_gaps"] == [["none", pytest.approx(0.010)],
                              ["bench.wait", pytest.approx(0.005)]]


def test_combine_takes_device_means():
    a = trace_reduce.reduce_events([("x", 0, 10)], [], (0, 20), [])
    b = trace_reduce.reduce_events([("x", 0, 20)], [], (0, 20), [])
    c = trace_reduce.combine([a, b], ["/device:TPU:0", "/device:TPU:1"])
    assert c["busy_s"] == pytest.approx(15e-9)
    assert c["idle_share"] == pytest.approx(0.25)
    assert c["device_ops"] == [["x", pytest.approx(15e-9)]]


def test_self_time_and_short_names():
    ops = [("%while.1 = (s32[]) while(...)", 0, 100),
           ("%paged_attention.5 = bf16[32,5,3,64] custom-call(...)", 10, 60),
           ("%fusion.2 = f32[8] fusion(...)", 70, 80)]
    r = trace_reduce.reduce_events(ops, [], (0, 100), [])
    assert r["ops"] == {"while.1": 40e-9, "paged_attention.5": 50e-9,
                        "fusion.2": 10e-9}


FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "small.xplane.pb"
# by hand from the fixture's events: the traced window is the host span
# [43,694,581, 109,419,122] ns; the three calls' ops, clipped to it, cover
# 91,618 + 180,841 + 180,869 ns
FIXTURE_WINDOW_S = 0.065724541
FIXTURE_BUSY_S = 0.000453328
FIXTURE_IDLE = 1 - FIXTURE_BUSY_S / FIXTURE_WINDOW_S


def _brute(ops, window, step_ns=1000):
    """Busy time by sampling every microsecond: independent of the
    interval arithmetic under test."""
    w0, w1 = window
    n = int((w1 - w0) // step_ns)
    busy = bytearray(n)
    for _, s, e in ops:
        a = max(0, int((s - w0) // step_ns))
        b = min(n, int((e - w0) // step_ns))
        busy[a:b] = b"\x01" * max(0, b - a)
    return sum(busy) * step_ns / 1e9, n * step_ns / 1e9


def test_recorded_chip_trace():
    pd = trace_reduce.load(FIXTURE)
    (_, w0, w1), = trace_reduce.host_spans(pd, {"bench.traced"})
    plane, = trace_reduce.device_planes(pd)
    ops = [ev for ln in plane.lines if ln.name == trace_reduce.OPS_LINE
           for ev in trace_reduce._events(ln)]
    red = trace_reduce.reduce(FIXTURE, window="bench.traced",
                              annotations=("bench.submit", "bench.wait"))
    busy, window = _brute(ops, (w0, w1))
    assert red["window_s"] == pytest.approx(FIXTURE_WINDOW_S, rel=1e-9)
    assert red["window_s"] == pytest.approx(window, abs=2e-6)
    assert red["busy_s"] == pytest.approx(busy, abs=2e-5)
    assert red["busy_s"] == pytest.approx(FIXTURE_BUSY_S, rel=1e-6)
    assert red["idle_share"] == pytest.approx(FIXTURE_IDLE, rel=1e-6)
    # one chip: no collectives, so nothing exposed
    assert red["collective_exposed_s"] == 0.0
    # the host slept 20 ms three times under bench.wait with the chip idle
    waits = [g for g in red["idle_gaps"] if g[0] == "bench.wait"]
    assert len(waits) >= 3 and all(g[1] > 0.019 for g in waits[:3])
