"""The paper's bilevel loop through ``Engine.run`` with fused dispatch and a
device-resident node sampler: each eval interval is one device program.

Set-up builds one ``Engine`` and drives it through a warm-up run of two
intervals, which builds every program and whose second interval calibrates
how many steps fill ``--seconds``. The timed run is a fresh ``Engine.run``
of that engine from the seed; its window runs from the ``on_eval`` call at
t=0 to the last one. ``check`` follows the run's first interval with the
float32 reference (``bench/reference/bilevel.py``).
"""
from __future__ import annotations

import time

import numpy as np

from bench import weights
from bench.common import annotate
from bench.reference import bilevel


def ring_weights(K: int, self_weight: float) -> np.ndarray:
    nb = (1.0 - self_weight) / 2.0
    W = np.eye(K) * self_weight
    for k in range(K):
        W[k, (k - 1) % K] += nb
        W[k, (k + 1) % K] += nb
    return W


def engine_seed(seed: int) -> int:
    """The run's integer seed: ``Engine.run`` keys on 32 bits."""
    return int(seed) % (2 ** 31 - 1)


def build(cell: dict, cfg: dict, seed: int):
    """(engine, sampler, eval batch, x0, y0, data) of one run."""
    import jax
    import jax.numpy as jnp
    from repro.core import HParams, HypergradConfig, logreg_hyperopt
    from repro.core.engine import Engine
    from repro.core.topology import Topology
    from repro.data import Dataset, make_device_sampler

    run = cell["run"]
    K, J, L = run["nodes"], run["J"], float(run["lip_gy"])
    data = weights.classification(cfg["data"], K, seed)
    tr_a, tr_b, va_a, va_b = data
    sampler = make_device_sampler(
        [Dataset(tr_a[k], tr_b[k]) for k in range(K)],
        [Dataset(va_a[k], va_b[k]) for k in range(K)],
        batch=run["batch_total"] // K, J=J)
    d, c = cfg["data"]["d"], cfg["data"]["classes"]
    prob = logreg_hyperopt(d=d, c=c, lip_gy=L)
    topo = Topology("ring", K, ring_weights(K, run["self_weight"]))
    eng = Engine(prob, HypergradConfig(J=J, lip_gy=L, randomize=True),
                 HParams(**run["hp"]), topo, algo=run["algo"],
                 mix=run["mix"], dispatch="fused",
                 self_weight=run["self_weight"])
    eval_batch = {"a": va_a.reshape(-1, d), "b": va_b.reshape(-1)}
    x0 = jnp.zeros((d,), jnp.float32)
    y0 = 0.01 * jax.random.normal(weights.key_of(seed, 4), (d, c))
    return eng, sampler, eval_batch, x0, y0, data


def run(ctx, cell: dict, cfg: dict, seed: int, seconds: float) -> dict:
    import jax
    import jax.numpy as jnp

    E = int(cell["run"]["eval_every"])
    eng, sampler, eval_batch, x0, y0, data = build(cell, cfg, seed)
    snap = {}
    copy = lambda s: jax.tree.map(jnp.copy, s)

    # warm-up: the same engine, sampler and callbacks; times its intervals
    stamps = {}

    def on_warm(t, state):
        with annotate("bench.eval"):
            if t <= E:
                snap["warm"] = copy(state)
            stamps[t] = time.perf_counter()

    with annotate("bench.engine_run"):
        eng.run(sampler, eval_batch, steps=2 * E, seed=engine_seed(seed) + 1,
                eval_every=E, x0=x0, y0=y0, on_eval=on_warm)
    interval_s = stamps[2 * E] - stamps[E]
    steps = E * max(2, int(round(seconds / interval_s)))
    # the run slices its (steps, 2) key schedule once per interval: build
    # that slice for this length now, not inside the window
    jax.block_until_ready(jax.random.split(jax.random.PRNGKey(0), steps)[:E])

    times = {}
    trace_at = int(cell.get("trace_at", 0.4) * steps) // E * E
    trace_n = max(1, int(round(float(cell.get("trace_s", 2.0))
                               / interval_s))) * E

    def on_eval(t, state):
        if t == 0:
            ctx.open_window()
        with annotate("bench.eval"):
            if t <= E:
                snap[t] = copy(state)
            times[t] = time.perf_counter()
            if ctx.trace and t == trace_at and ctx.trace_t0 is None:
                ctx.start_trace()
            elif ctx.tracing and t == trace_at + trace_n:
                ctx.stop_trace()
        if t == steps:
            ctx.close_window()

    with annotate("bench.engine_run"):
        res = eng.run(sampler, eval_batch, steps=steps,
                      seed=engine_seed(seed), eval_every=E, x0=x0, y0=y0,
                      on_eval=on_eval)
    if ctx.tracing:
        ctx.stop_trace()
    t0 = times[0]
    traced_steps = None
    if ctx.trace_t0 is not None:
        traced_steps = min(trace_n, steps - trace_at)
    prog = {t: jax.tree.map(np.asarray, s._asdict())
            for t, s in snap.items() if t in (0, E)}
    return {
        "kind": "bilevel", "steps": steps, "eval_every": E,
        "window_s": times[steps] - t0, "interval_s": interval_s,
        "trace_window": (None if ctx.trace_t0 is None else
                         (ctx.trace_t0 - t0, ctx.trace_t1 - t0)),
        "traced_steps": traced_steps,
        "eval": {"upper": list(res.upper_loss), "lower": list(res.lower_loss)},
        "program": prog, "x0": np.asarray(x0), "y0": np.asarray(y0),
        "data": data, "eval_batch": (eval_batch["a"], eval_batch["b"]),
        "run": cell["run"], "shapes": _shapes(cell, cfg),
    }


def _shapes(cell: dict, cfg: dict) -> dict:
    run = cell["run"]
    return {"K": run["nodes"], "batch": run["batch_total"] // run["nodes"],
            "d": cfg["data"]["d"], "c": cfg["data"]["classes"], "J": run["J"]}


def _norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


def reference(records: dict, seed: int, dtype=None) -> dict:
    import jax.numpy as jnp
    run = records["run"]
    return bilevel.run(
        records["data"], ring_weights(run["nodes"], run["self_weight"]),
        records["x0"], records["y0"], seed=engine_seed(seed),
        steps=records["eval_every"], J=run["J"], L=float(run["lip_gy"]),
        batch=run["batch_total"] // run["nodes"], hp=run["hp"],
        eval_batch=records["eval_batch"], dtype=dtype or jnp.float32)


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers of a run (``prog``: program states at t=0 and
    t=E and its evaluations) against the reference.

    * ``loss_gap`` — worst relative gap of the upper and lower validation
      losses at t=0 and t=E;
    * ``grad_gap`` — worst leaf gap between the norms of the first
      gradients as the optimiser holds them after the first step (``u``:
      the hypergradient; ``v``: the lower gradient), against the larger of
      the reference leaf's norm and the median leaf's;
    * ``delta_gap`` — the same for the parameters' change over the
      interval; leaves whose reference gradient is under a thousandth of
      the median leaf's are left out."""
    losses = [(prog["eval"][k][i], float(ref[e][k]))
              for k in ("upper", "lower") for i, e in ((0, "eval0"),
                                                       (1, "evalE"))]
    loss_gap = max(abs(p - r) / abs(r) for p, r in losses)
    p0, r0 = prog["program"][0], ref["t0"]
    g_ref = {k: _norm(r0[k]) for k in ("u", "v")}
    med = float(np.median(list(g_ref.values())))
    grad_gap = max(abs(_norm(p0[k]) - g_ref[k]) / max(g_ref[k], med)
                   for k in ("u", "v"))
    pE, rE = prog["program"][prog["eval_every"]], ref["tE"]
    base = {"x": ref["X0"], "y": ref["Y0"]}
    moved = {"x": "u", "y": "v"}
    d_ref = {k: _norm(rE[k] - base[k]) for k in base
             if g_ref[moved[k]] >= 1e-3 * med}
    dmed = float(np.median(list(d_ref.values())))
    delta_gap = max(abs(_norm(pE[k] - base[k]) - d_ref[k]) / max(d_ref[k],
                                                                 dmed)
                    for k in d_ref)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "delta_gap": delta_gap}


def check(cell: dict, cfg: dict, seed: int, records: dict) -> dict:
    """Numbers compared, each with its limit: ``{name: (value, limit)}``;
    a run is correct when every value is at most its limit."""
    got = readings(records, reference(records, seed))
    lim = cell["check"]
    out = {k: (v, float(lim[k])) for k, v in got.items()}
    finite = all(np.isfinite(v) for v in records["eval"]["upper"]
                 + records["eval"]["lower"])
    out["nonfinite_evals"] = (0 if finite else 1, 0)
    return out


def control(cell: dict, cfg: dict, seed: int, records: dict) -> dict:
    """The compared numbers with the control in the program's place: the
    reference run in bfloat16, read against the float32 reference."""
    import jax.numpy as jnp
    low = reference(records, seed, dtype=jnp.bfloat16)
    E = records["eval_every"]
    as_prog = {"eval": {k: [float(low["eval0"][k]), float(low["evalE"][k])]
                        for k in ("upper", "lower")},
               "program": {0: low["t0"], E: low["tE"]}, "eval_every": E}
    return readings(as_prog, reference(records, seed))
