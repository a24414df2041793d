"""The serving traffic generator: fixed work per seed, limits respected."""
import numpy as np

from bench import traffic

MIX = {"rate_per_s": 10.0, "size_seed": 3,
       "prompt": {"median": 512, "sigma": 0.7, "min": 32, "max": 1792},
       "output": {"median": 128, "sigma": 0.5, "min": 16, "max": 256},
       "max_total": 2048}


def test_every_seed_gets_the_same_schedule_with_other_tokens():
    a = traffic.serve_requests(MIX, 1, 30.0, 49152)
    b = traffic.serve_requests(MIX, 2 ** 33 + 5, 30.0, 49152)
    assert len(a) == len(b) == 300
    schedule = lambda rs: [(r.due_s, len(r.prompt), r.max_new) for r in rs]
    assert schedule(a) == schedule(b)
    assert len({len(r.prompt) for r in a}) > 50
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_same_seed_same_requests():
    a = traffic.serve_requests(MIX, 9, 10.0, 49152)
    b = traffic.serve_requests(MIX, 9, 10.0, 49152)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_limits_and_window():
    rs = traffic.serve_requests(MIX, 4, 30.0, 49152)
    due = [r.due_s for r in rs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30.0
    for r in rs:
        assert 32 <= len(r.prompt) <= 1792 and 1 <= r.max_new <= 256
        assert len(r.prompt) + r.max_new <= 2048
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 49152


def test_shared_prefix_and_bursts():
    mix = dict(MIX, arrivals="on_off", on_s=2.0, off_s=3.0,
               shared_prefix={"count": 4, "tokens": 64, "zipf_a": 1.1},
               prompt={"median": 32, "sigma": 0.5, "min": 8, "max": 128})
    rs = traffic.serve_requests(mix, 5, 20.0, 1000)
    heads = {tuple(r.prompt[:64]) for r in rs}
    assert 1 < len(heads) <= 4
    assert all(len(r.prompt) >= 64 + 8 for r in rs)
    # no request is due inside an off period
    assert all((r.due_s % 5.0) <= 2.0 + 1e-9 for r in rs)
