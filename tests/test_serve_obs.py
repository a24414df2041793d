"""ServeEngine observability: counters/latency stats against a forced-
preemption paged trace, the loop's spans on the profiler's clock, and the
bitwise stream contract with obs enabled.

Serve obs is host-side only (counters, spans, and timestamps taken at chunk
boundaries the scheduler already crosses), so a live Recorder must not
perturb a single emitted token. The workload here is the same pool-starved
trace as tests/test_paged.py::test_paged_preemption_preserves_streams —
every preemption, admission retry, and restart shows up in the registry.
"""
import jax
import numpy as np
import pytest

from repro.configs import get
from repro.models import init_params
from repro.obs import INSTANTS, SPANS, Recorder, SpanTracer
from repro.serve import ServeEngine

PROMPT_BUDGETS = [9, 8, 10, 7, 9]


@pytest.fixture(scope="module")
def model():
    cfg = get("smollm-360m").reduced().with_overrides(
        d_model=32, n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _workload(cfg):
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=rng.integers(4, 12))
               for _ in PROMPT_BUDGETS]
    return list(zip(prompts, PROMPT_BUDGETS))


def _paged(model, recorder=None):
    cfg, params = model
    return ServeEngine(cfg, params, capacity=32, max_batch=4, decode_chunk=4,
                       mode="paged", block_size=4, num_blocks=7,
                       recorder=recorder)


def _drain(eng, workload):
    rids = [eng.submit(p, m) for p, m in workload]
    return rids, eng.run()


def test_preemption_and_admission_counters(model):
    rec = Recorder(tracer=SpanTracer())
    eng = _paged(model, recorder=rec)
    rids, results = _drain(eng, _workload(model[0]))
    snap = rec.snapshot()
    assert eng.stats["preemptions"] > 0, "workload must exercise preemption"
    assert snap["counters"]["serve_preemptions"] == eng.stats["preemptions"]
    assert snap["counters"]["serve_admission_rejects"] > 0
    assert snap["counters"]["serve_submitted"] == len(rids)
    assert snap["counters"]["serve_finished"] == len(rids)
    # every preemption leaves an instant marker carrying the victim rid
    marks = [e for e in rec.tracer.to_chrome_trace()["traceEvents"]
             if e.get("ph") == "i" and e["name"] == "serve.preempt"]
    assert len(marks) == eng.stats["preemptions"]
    assert all(m["args"]["rid"] in rids for m in marks)


def test_per_request_ttft_and_latency(model):
    rec = Recorder()
    eng = _paged(model, recorder=rec)
    rids, results = _drain(eng, _workload(model[0]))
    done = {e["rid"]: e for e in rec.events if e["kind"] == "request_done"}
    assert sorted(done) == sorted(rids)
    for rid in rids:
        req, ev = eng.completed[rid], done[rid]
        assert ev["tokens"] == len(results[rid])
        assert ev["ttft_s"] == pytest.approx(
            req.first_token_s - req.submit_s)
        assert ev["latency_s"] == pytest.approx(req.finish_s - req.submit_s)
        assert 0.0 < ev["ttft_s"] <= ev["latency_s"]
    obs = rec.snapshot()["observations"]
    assert obs["serve_ttft_s"]["count"] == len(rids)
    assert obs["serve_latency_s"]["p95"] >= obs["serve_ttft_s"]["p50"]


def test_streams_identical_obs_on_off(model):
    """The bitwise stream contract: a live Recorder + SpanTracer must not
    change one emitted token, nor the scheduler's preemption trace."""
    workload = _workload(model[0])
    _, off = _drain(_paged(model), workload)
    rec = Recorder(tracer=SpanTracer())
    eng_on = _paged(model, recorder=rec)
    _, on = _drain(eng_on, workload)
    assert off == on
    # host scheduling is pure → the obs counters are deterministic too
    rec2 = Recorder()
    eng2 = _paged(model, recorder=rec2)
    _drain(eng2, workload)
    assert (rec.snapshot()["counters"]["serve_admission_rejects"]
            == rec2.snapshot()["counters"]["serve_admission_rejects"])
    assert (rec.snapshot()["counters"]["serve_preemptions"]
            == rec2.snapshot()["counters"]["serve_preemptions"])


def test_submit_reject_counter(model):
    cfg, params = model
    rec = Recorder()
    eng = ServeEngine(cfg, params, capacity=32, max_batch=2, mode="paged",
                      block_size=4, num_blocks=4, recorder=rec)
    with pytest.raises(ValueError, match="blocks"):
        eng.submit(np.arange(10), max_new_tokens=10)
    assert rec.snapshot()["counters"]["serve_submit_rejects"] == 1


def test_recorder_default_is_null(model):
    eng = _paged(model)
    assert eng.recorder.enabled is False


def test_boundary_gauges_and_drain_stats(model):
    rec = Recorder()
    eng = _paged(model, recorder=rec)
    _drain(eng, _workload(model[0]))
    snap = rec.snapshot()
    g, c = snap["gauges"], snap["counters"]
    # peaks are gauges, counts are counters: one type per name
    assert 0 < g["serve_peak_blocks_in_use"] <= eng.pool.num_blocks
    assert g["serve_peak_concurrency"] == eng.stats["peak_concurrency"]
    assert g["serve_tokens_per_sec"] > 0
    assert c["serve_preemptions"] == eng.stats["preemptions"]
    assert c["serve_live_slot_steps"] == eng.stats["live_slot_steps"]
    assert not set(g) & set(c)
    samples = [ln.split()[0] for ln in rec.prometheus_text().splitlines()
               if not ln.startswith("#")]
    assert len(samples) == len(set(samples))        # no name sampled twice


def test_paged_spans_stamps_and_occupancy(model, profiled_spans):
    """A trace-only recorder puts the paged loop's phases on the profiler's
    host plane: each prefill carries its rid and sits inside its request's
    serve.admit, and no phase overlaps another. Every request's admission
    stamp lies between its submission and its first token, and the slot
    steps dispatched never exceed the decode steps times the slots. The
    streams are the recorder-off streams."""
    workload = _workload(model[0])
    _, off = _drain(_paged(model), workload)
    rec = Recorder(tracer=SpanTracer(), metrics=False)
    eng = _paged(model, recorder=rec)
    (rids, on), spans = profiled_spans(lambda: _drain(eng, workload))
    assert on == off
    assert rec.snapshot()["counters"] == {}          # traces only

    mem = [e for e in rec.tracer.events if e["ph"] in ("X", "i")]
    assert {e["name"] for e in mem if e["ph"] == "X"} <= set(SPANS)
    assert {e["name"] for e in mem if e["ph"] == "i"} <= set(INSTANTS)
    prefills = [e for e in mem if e["name"] == "serve.prefill"]
    assert len(prefills) == eng.stats["prefills"]
    assert {e["args"]["rid"] for e in prefills} == set(rids)

    by = {n: [(s, e) for m, s, e in spans if m == n] for n in SPANS}
    assert len(by["serve.prefill"]) == eng.stats["prefills"]
    assert len(by["serve.decode_dispatch"]) == eng.stats["decode_dispatches"]
    for s, e in by["serve.prefill"]:
        assert any(a <= s and e <= b for a, b in by["serve.admit"])
    top = sorted(iv for n in ("serve.block_budget", "serve.decode_dispatch",
                              "serve.decode_wait", "serve.emit")
                 for iv in by[n])
    assert all(e <= s for (_, e), (s, _) in zip(top, top[1:]))

    for rid in rids:
        r = eng.completed[rid]
        assert r.submit_s <= r.admit_s <= r.first_token_s <= r.finish_s
    st = eng.stats
    assert 0 < st["live_slot_steps"] <= st["decode_steps"] * eng.max_batch


def test_queue_wait_observed(model):
    rec = Recorder()
    eng = _paged(model, recorder=rec)
    rids, _ = _drain(eng, _workload(model[0]))
    q = rec.snapshot()["observations"]["serve_queue_s"]
    assert q["count"] == len(rids)
    waits = [eng.completed[r].admit_s - eng.completed[r].submit_s
             for r in rids]
    assert q["max"] == pytest.approx(max(waits)) and min(waits) >= 0.0
