"""repro.obs: metric-set semantics, tracer/recorder units, and the engine
integration contracts — obs-on must be bitwise invisible to training.

The load-bearing assertions are the bitwise ones: a fused run with a live
Recorder produces the exact same final state (and eval losses) as the same
run with the NullRecorder default, and still matches per-step dispatch —
the metric accumulator rides the scan carry without touching the
algorithm's op stream.
"""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HParams, HypergradConfig, logreg_hyperopt, ring
from repro.core.engine import Engine, key_schedule
from repro.data import (NodeSampler, make_classification, make_device_sampler,
                        shard_to_nodes, train_val_split)
from repro.obs import (SPANS, MetricSet, MetricSpec, NullRecorder, Recorder,
                       SpanTracer, cli_recorder)

K, D, J = 4, 8, 2


@pytest.fixture(scope="module")
def setup():
    ds = make_classification(n=400, d=D, c=2, seed=1)
    tr, va = train_val_split(ds, 0.3, seed=1)
    tr_nodes, va_nodes = shard_to_nodes(tr, K), shard_to_nodes(va, K)
    sample = make_device_sampler(tr_nodes, va_nodes, batch=8, J=J)
    prob = logreg_hyperopt(d=D, c=2, lip_gy=5.0)
    cfg = HypergradConfig(J=J, lip_gy=5.0, randomize=True)
    eval_batch = {"a": jnp.asarray(va.a[:64]), "b": jnp.asarray(va.b[:64])}
    return prob, cfg, HParams(eta=0.1), sample, eval_batch


def _leaves_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# MetricSet semantics (pure device-side accumulation)
# ---------------------------------------------------------------------------

def _toy_set():
    return MetricSet([
        MetricSpec("ones", "counter", lambda ctx: jnp.float32(1.0)),
        MetricSpec("val", "mean", lambda ctx: ctx["new"]),
        # hist fns return the per-step (bins,) count vector themselves
        # (cf. staleness_hist_fn); the accumulator just adds
        MetricSpec("ages", "hist",
                   lambda ctx: jnp.bincount(
                       jnp.clip(ctx["old"], 0, 2), length=3), bins=3),
    ])


def test_metric_set_kinds_accumulate():
    ms = _toy_set()
    acc = ms.init()
    ages = jnp.array([0, 2, 2, 9], jnp.int32)   # 9 clips into the last bin
    for v in (2.0, 4.0):
        acc = ms.update(acc, {"old": ages, "new": jnp.float32(v)})
    rows = {name: (kind, val) for name, kind, val in ms.drain(acc)}
    assert rows["ones"] == ("counter", pytest.approx(2.0))
    assert rows["val"] == ("mean", pytest.approx(3.0))   # (2+4)/2
    kind, hist = rows["ages"]
    assert kind == "hist"
    np.testing.assert_array_equal(np.asarray(hist), [2, 0, 6])


def test_metric_set_update_is_jittable():
    ms = _toy_set()
    step = jax.jit(lambda a, ctx: ms.update(a, ctx))
    acc = step(ms.init(), {"old": jnp.zeros(2, jnp.int32),
                           "new": jnp.float32(5.0)})
    rows = {n: v for n, _, v in ms.drain(acc)}
    assert rows["val"] == pytest.approx(5.0)


def test_metric_spec_validates():
    with pytest.raises(ValueError):
        MetricSpec("h", "hist", lambda ctx: ctx["old"])      # bins missing
    with pytest.raises(ValueError):
        MetricSpec("x", "gauge", lambda ctx: 0.0)            # unknown kind


def test_empty_metric_set_is_falsy():
    ms = MetricSet([])
    assert len(ms) == 0 and ms.drain(ms.init()) == []


# ---------------------------------------------------------------------------
# SpanTracer → Chrome trace events
# ---------------------------------------------------------------------------

def test_tracer_spans_nest_and_export(tmp_path):
    tr = SpanTracer(process_name="t")
    with tr.span("outer", step=1):
        with tr.span("inner"):
            pass
        tr.instant("mark", n=2)
    doc = tr.to_chrome_trace()
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert phases.count("X") == 2 and "i" in phases and "M" in phases
    inner, outer = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert inner["name"] == "inner" and outer["name"] == "outer"
    # containment: inner lies inside outer on the same timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    path = tr.write(str(tmp_path))          # dir → dir/trace.json
    with open(path) as f:
        assert json.load(f)["traceEvents"]


def test_tracer_spans_reach_the_profiler(profiled_spans):
    """A SpanTracer span is also a TraceAnnotation: inside a profiler
    session it lands on the host plane, nested as in the code, and it is
    still kept for the Chrome export."""
    tr = SpanTracer()

    def body():
        with tr.span("engine.eval", step=3):
            with tr.span("engine.on_eval", step=3):
                jnp.ones(3).sum().block_until_ready()

    _, spans = profiled_spans(body)
    (n0, s0, e0), (n1, s1, e1) = spans
    assert (n0, n1) == ("engine.eval", "engine.on_eval")
    assert s0 <= s1 and e1 <= e0
    assert [e["name"] for e in tr.events if e["ph"] == "X"] == [
        "engine.on_eval", "engine.eval"]


def test_tracer_span_closes_on_exception():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError
    assert any(e.get("name") == "boom" and "dur" in e
               for e in tr.to_chrome_trace()["traceEvents"])


# ---------------------------------------------------------------------------
# Recorder / NullRecorder
# ---------------------------------------------------------------------------

def test_null_recorder_is_inert():
    rec = NullRecorder()
    assert not rec.enabled
    rec.counter_add("x"), rec.gauge_set("g", 1.0), rec.observe("o", 0.5)
    with rec.span("s"):
        pass
    assert rec.snapshot() == {}


def test_recorder_snapshot_and_prometheus(tmp_path):
    rec = Recorder(jsonl_path=str(tmp_path / "m.jsonl"))
    rec.counter_add("steps", 3)
    rec.gauge_set("loss", 0.25)
    for v in (1.0, 2.0, 3.0, 4.0):
        rec.observe("lat", v)
    rec.record_drain([("c", "counter", 2.0), ("m", "mean", 0.5),
                      ("h", "hist", np.array([1, 2]))], step=7)
    snap = rec.snapshot()
    assert snap["counters"]["steps"] == 3 and snap["counters"]["c"] == 2.0
    assert snap["gauges"]["loss"] == 0.25 and snap["gauges"]["m"] == 0.5
    assert snap["observations"]["lat"]["count"] == 4
    assert snap["observations"]["lat"]["p50"] == pytest.approx(2.5)
    assert snap["hist_counts"]["h"] == [1, 2]
    text = rec.prometheus_text()
    assert "# TYPE steps counter" in text and "# TYPE lat summary" in text
    assert 'h_bucket{le="1"}' in text       # cumulative histogram buckets
    rec.flush()
    lines = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
    assert any(e["kind"] == "drain" and e["step"] == 7 for e in lines)
    rec.close()


def test_prometheus_name_sanitization():
    rec = Recorder()
    rec.gauge_set("serve/tok-s", 1.0)
    assert "serve_tok_s 1" in rec.prometheus_text().replace(".0", "")


def test_trace_only_recorder_records_no_metric():
    rec = Recorder(tracer=SpanTracer(), metrics=False)
    assert not rec.enabled
    rec.counter_add("x"), rec.gauge_set("g", 1.0), rec.observe("o", 0.5)
    rec.event("e", v=1)
    with rec.span("engine.eval"):
        pass
    snap = rec.snapshot()
    assert not (snap["counters"] or snap["gauges"] or snap["observations"]
                or rec.events)
    assert any(e.get("name") == "engine.eval" for e in rec.tracer.events)


def test_cli_recorder_off_and_on(tmp_path):
    rec, fin = cli_recorder(None, None)
    assert isinstance(rec, NullRecorder) and fin() == []
    rec, fin = cli_recorder(None, str(tmp_path / "t0"))
    assert rec.tracer is not None and not rec.enabled   # trace only
    rec, fin = cli_recorder(str(tmp_path / "m"), str(tmp_path / "t"))
    rec.counter_add("x")
    with rec.span("s"):
        pass
    paths = fin()
    names = {p.split("/")[-1] for p in paths}
    assert {"metrics.prom", "trace.json"} <= names


# ---------------------------------------------------------------------------
# Engine integration: obs must be bitwise invisible
# ---------------------------------------------------------------------------

def test_fused_obs_on_bitwise_equals_obs_off(setup):
    """7 steps / eval_every=3 exercises full AND partial chunks with the
    metric accumulator in the carry."""
    prob, cfg, hp, sample, eval_batch = setup
    out = {}
    for name, rec in (("off", None), ("on", Recorder())):
        eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="ring_rolled",
                     recorder=rec)
        out[name] = eng.run(sample, eval_batch, steps=7, eval_every=3,
                            seed=0, return_state=True)
    (r_off, s_off), (r_on, s_on) = out["off"], out["on"]
    _leaves_equal(s_off, s_on)
    assert r_off.upper_loss == r_on.upper_loss


def test_fused_obs_on_bitwise_equals_per_step(setup):
    prob, cfg, hp, sample, eval_batch = setup
    rec = Recorder()
    fused = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="ring_rolled",
                   dispatch="fused", recorder=rec)
    per = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="ring_rolled",
                 dispatch="per_step")
    _, sf = fused.run(sample, eval_batch, steps=7, eval_every=3, seed=0,
                      return_state=True)
    _, sp = per.run(sample, eval_batch, steps=7, eval_every=3, seed=0,
                    return_state=True)
    _leaves_equal(sf, sp)


def test_trainer_metrics_populate_registry(setup):
    prob, cfg, hp, sample, eval_batch = setup
    rec = Recorder()
    eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="ring_rolled",
                 recorder=rec)
    eng.run(sample, eval_batch, steps=6, eval_every=3, seed=0)
    snap = rec.snapshot()
    assert snap["counters"]["train_steps"] == 6
    assert snap["counters"]["train_mix_bytes"] > 0
    for g in ("train_consensus_x", "train_consensus_y",
              "train_update_norm_x", "train_update_norm_y",
              "eval_upper_loss", "eval_consensus_x"):
        assert g in snap["gauges"], g
    assert snap["gauges"]["train_update_norm_x"] > 0.0


def test_async_gossip_staleness_histogram(setup):
    """The realized per-edge age distribution lands in the registry: tau+1
    bins, counts totalling (mix sites x 2 directions x K nodes) per step,
    stale-by-0 the majority at a mild drop rate."""
    prob, cfg, hp, sample, eval_batch = setup
    tau, steps = 2, 6
    rec = Recorder()
    eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="async_gossip",
                 mix_kwargs={"tau": tau, "drop_prob": 0.3}, recorder=rec)
    eng.run(sample, eval_batch, steps=steps, eval_every=3, seed=0)
    counts = rec.snapshot()["hist_counts"]["train_staleness"]
    assert len(counts) == tau + 1
    total = int(sum(counts))
    assert total > 0 and total % (2 * K * steps) == 0
    assert counts[0] == max(counts)         # fresh edges dominate


def test_per_step_dispatch_skips_in_scan_metrics(setup):
    """per_step dispatch records eval gauges + the step counter only — no
    in-scan accumulator, and no crash."""
    prob, cfg, hp, sample, eval_batch = setup
    rec = Recorder()
    eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="ring_rolled",
                 dispatch="per_step", recorder=rec)
    eng.run(sample, eval_batch, steps=4, eval_every=2, seed=0)
    snap = rec.snapshot()
    assert snap["counters"]["train_steps"] == 4
    assert "train_consensus_x" not in snap["gauges"]
    assert "eval_upper_loss" in snap["gauges"]


def _chunk_fingerprint(eng, state, steps):
    """sha256 of the program the run's fused chunk lowers to. The run must
    have built exactly one chunk, the one without the in-scan metric
    accumulator (an accumulator chunk takes two more arguments and fails to
    lower here). The compiled text carries per-compile ids; the lowered
    module does not."""
    (key, (_, chunk)), = [(k, v) for k, v in eng._jit_cache.items()
                          if isinstance(k, tuple) and k[0] == "chunk"]
    assert key[3] is False, "the run threaded the metric accumulator"
    kbs, kns = key_schedule(jax.random.PRNGKey(0), steps)
    text = chunk.lower(state, kbs, kns).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_fused_run_spans_on_the_profiler_clock(setup, profiled_spans):
    """A trace-only recorder puts the run loop's spans on the profiler's
    host plane: one engine.dispatch per chunk, engine.on_eval nested in
    engine.eval, and no chunk runs inside an eval. It compiles the same
    chunk as the NullRecorder, and the run is bitwise the same."""
    prob, cfg, hp, sample, eval_batch = setup
    steps, every = 7, 3
    seen = []
    runs = {}
    for name, rec in (("off", None),
                      ("trace", Recorder(tracer=SpanTracer(),
                                         metrics=False))):
        eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="ring_rolled",
                     recorder=rec)

        def go():
            return eng.run(sample, eval_batch, steps=steps, eval_every=every,
                           seed=0, return_state=True,
                           on_eval=lambda t, s: seen.append(t))

        if name == "trace":
            (res, state), spans = profiled_spans(go)
        else:
            res, state = go()
        runs[name] = (eng, res, state)
    (e_off, r_off, s_off), (e_tr, r_tr, s_tr) = runs["off"], runs["trace"]
    _leaves_equal(s_off, s_tr)
    assert r_off.upper_loss == r_tr.upper_loss
    # the trace-only recorder threads no metric accumulator: same chunk
    assert (_chunk_fingerprint(e_off, s_off, every)
            == _chunk_fingerprint(e_tr, s_tr, every))

    by = {n: [(s, e) for m, s, e in spans if m == n] for n in SPANS}
    chunks = -(-steps // every)
    assert len(by["engine.dispatch"]) == chunks
    assert len(by["engine.eval"]) == chunks + 1        # t=0 too
    assert len(by["engine.on_eval"]) == chunks + 1
    assert not by["engine.drain"]                      # metrics are off
    for s, e in by["engine.on_eval"]:
        assert any(es <= s and e <= ee for es, ee in by["engine.eval"])
    for s, e in by["engine.dispatch"]:
        assert not any(es < e and s < ee for es, ee in by["engine.eval"])
    # in memory, the same spans for the Chrome export
    names = [e["name"] for e in e_tr.recorder.tracer.events
             if e["ph"] == "X"]
    assert names.count("engine.dispatch") == chunks
    assert set(names) <= set(SPANS)


def test_metrics_recorder_drains_under_its_span(setup):
    """With metrics on, the in-scan drain runs once per chunk under
    engine.drain."""
    prob, cfg, hp, sample, eval_batch = setup
    rec = Recorder(tracer=SpanTracer())
    eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="ring_rolled",
                 recorder=rec)
    eng.run(sample, eval_batch, steps=6, eval_every=3, seed=0)
    names = [e["name"] for e in rec.tracer.events if e["ph"] == "X"]
    assert names.count("engine.drain") == 2
    assert sum(1 for e in rec.events if e["kind"] == "drain") == 2


def test_per_step_dispatch_spans(setup):
    prob, cfg, hp, sample, eval_batch = setup
    rec = Recorder(tracer=SpanTracer(), metrics=False)
    eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="ring_rolled",
                 dispatch="per_step", recorder=rec)
    eng.run(sample, eval_batch, steps=4, eval_every=2, seed=0)
    names = [e["name"] for e in rec.tracer.events if e["ph"] == "X"]
    assert names.count("engine.dispatch") == 4
    assert names.count("engine.eval") == 3
