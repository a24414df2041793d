"""Open-loop serving through ``ServeEngine.submit`` / ``ServeEngine.stream``.

Requests come from ``bench/traffic.py`` at the cell's fixed rate and are
submitted when due, at the next point where the engine hands control back
(after a prefill or a decode chunk). Each request is timed from when it was
due, so a stall counts against every request it delays; how late the
submissions ran is reported beside it.

The window opens when the first request is due and closes when the last one
is. The engine then drains what was admitted, for at most a minute; a
request that has not finished by then counts as missing.

``check`` compares what the timed path served with the plain float32
reference of the configuration's architecture (``bench/models/<model_type>.py``)
on a sample drawn from the seed.
"""
from __future__ import annotations

import time

import numpy as np

from bench import models, traffic
from bench.common import annotate
from bench.reference import gap_of

DRAIN_S = 60.0
# ServeEngine.stats counts summed over the drains of a run
SUMMED = ("prefills", "prefill_s", "decode_steps", "preemptions",
          "paged_grid_steps", "paged_live_grid_steps", "paged_live_pages")


def warm_prompts(cell: dict) -> list[int]:
    """Prompt lengths whose drains build every program the window can use:
    each power-of-two prefill bucket, exact and padded, and through their
    decode chunks every block-table width."""
    mix = cell["traffic"]
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    if "shared_prefix" in mix:
        lo += mix["shared_prefix"]["tokens"]
        hi += mix["shared_prefix"]["tokens"]
    out, b = [], 8
    while b < 2 * hi:
        for n in (b - 1, b):
            if lo <= n <= hi:
                out.append(n)
        b *= 2
    return sorted(set(out + [lo, hi]))


def _warm(eng, cell: dict, vocab: int) -> None:
    rng = np.random.default_rng(0)
    budget = int(cell.get("warm_tokens", 16))
    for n in warm_prompts(cell):
        eng.submit(rng.integers(0, vocab, n, dtype=np.int32),
                   max_new_tokens=budget)
        eng.run()    # one at a time: each drain walks its own table widths


def run(ctx, cell: dict, cfg: dict, seed: int, seconds: float) -> dict:
    import jax
    from repro.serve import ServeEngine

    model = models.of(cfg)
    mcfg = model.program_config(cfg)
    params = model.weights(cfg, seed)
    jax.block_until_ready(params)
    eng = ServeEngine(mcfg, params, **cell["engine"])
    _warm(eng, cell, mcfg.vocab)
    reqs = traffic.serve_requests(cell["traffic"], seed, seconds, mcfg.vocab)
    rec = _drive(ctx, eng, reqs, seconds, cell)
    rec.update(kind="serve", params=params, cell=cell["name"],
               decode_chunk=eng.decode_chunk, seconds=seconds)
    del eng   # frees the KV pool before the reference runs
    return rec


def _drive(ctx, eng, reqs, seconds: float, cell: dict) -> dict:
    n = len(reqs)
    rid_of: dict[int, int] = {}           # engine rid -> request index
    out = [[] for _ in range(n)]          # tokens as the client saw them
    t_first = [None] * n
    t_last = [None] * n
    late = [0.0] * n
    deltas = []          # (host time, request index, first index, count)
    totals = dict.fromkeys(SUMMED, 0)
    totals.update(prefill_s=0.0, drains=0, peak_blocks_in_use=0)
    trace_from = seconds * float(cell.get("trace_at", 0.4))
    trace_to = trace_from + float(cell.get("trace_s", 3.0))
    nxt = 0
    t0 = ctx.open_window()
    clock = lambda: time.perf_counter() - t0

    def submit_due(now):
        nonlocal nxt
        if nxt >= n or reqs[nxt].due_s > now:
            return
        with annotate("bench.submit"):
            while nxt < n and reqs[nxt].due_s <= now:
                r = reqs[nxt]
                rid_of[eng.submit(r.prompt, max_new_tokens=r.max_new)] = nxt
                late[nxt] = now - r.due_s
                nxt += 1

    def trace_step(now):
        if not ctx.trace:
            return
        if ctx.trace_t0 is None and now >= trace_from:
            ctx.start_trace()
        elif ctx.tracing and now >= trace_to:
            ctx.stop_trace()

    closed = False
    while True:
        now = clock()
        if not closed and now >= seconds:
            ctx.close_window()
            closed = True
        if now > seconds + DRAIN_S:
            break
        submit_due(now)
        trace_step(now)
        if not eng.scheduler.has_work():
            if nxt >= n:
                break
            with annotate("bench.wait"):
                time.sleep(max(0.0, reqs[nxt].due_s - clock()))
            continue
        gen = eng.stream()
        try:
            while True:
                with annotate("bench.stream_next"):
                    ev = next(gen, None)
                now = clock()
                if ev is None:
                    break
                rid, delta, _done = ev
                i = rid_of[rid]
                deltas.append((now, i, len(out[i]), len(delta)))
                if t_first[i] is None:
                    t_first[i] = now
                t_last[i] = now
                out[i].extend(int(t) for t in delta)
                if not closed and now >= seconds:
                    ctx.close_window()
                    closed = True
                if now > seconds + DRAIN_S:
                    break
                submit_due(now)
                trace_step(now)
        finally:
            gen.close()
        st = eng.stats
        totals["drains"] += 1
        for k in SUMMED:
            totals[k] += st.get(k, 0)
        totals["peak_blocks_in_use"] = max(totals["peak_blocks_in_use"],
                                           st.get("peak_blocks_in_use", 0))
    if ctx.tracing:
        ctx.stop_trace()
    if not closed:
        ctx.close_window()
    trace_window = None
    if ctx.trace_t0 is not None:
        trace_window = (ctx.trace_t0 - t0, ctx.trace_t1 - t0)
    return {
        "requests": [
            {"due_s": r.due_s, "prompt_len": len(r.prompt),
             "max_new": r.max_new, "prompt": r.prompt,
             "tokens": out[i], "first_s": t_first[i], "last_s": t_last[i],
             "late_s": late[i]}
            for i, r in enumerate(reqs)],
        "deltas": deltas, "engine": totals, "trace_window": trace_window,
        "window_s": ctx.t_close - t0, "end_s": clock(),
    }


def _sample(records: dict, seed: int, want_tokens: int) -> list[int]:
    """Finished requests to compare: the longest, then others drawn from
    the seed until ``want_tokens`` served tokens are covered."""
    reqs = records["requests"]
    done = [i for i, r in enumerate(reqs) if len(r["tokens"]) == r["max_new"]]
    if not done:
        return []
    longest = max(done, key=lambda i: reqs[i]["prompt_len"] + reqs[i]["max_new"])
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(seed ^ 0x5EED)
    rng.shuffle(rest)
    pick, total = [longest], reqs[longest]["max_new"]
    for i in rest:
        if total >= want_tokens:
            break
        pick.append(i)
        total += reqs[i]["max_new"]
    return pick


def served_gaps(records: dict, cfg: dict, pick, *, pad_to: int,
                control: bool = False) -> list[float]:
    """Per sampled request, the widest gap of a served token below the
    reference's best. With ``control`` the tokens are the ones the fp8
    control puts first at the same positions instead."""
    params, reqs = records["params"], records["requests"]
    reference = models.of(cfg).reference_logits
    widest = []
    for i in pick:
        r = reqs[i]
        ref = reference(params, cfg, r["prompt"], r["tokens"], pad_to=pad_to)
        toks = r["tokens"]
        if control:
            lq = reference(params, cfg, r["prompt"], r["tokens"],
                           pad_to=pad_to, quant="fp8")
            toks = np.asarray(lq).argmax(axis=-1)
        widest.append(float(gap_of(ref, toks).max()))
    return widest


def check(cell: dict, cfg: dict, seed: int, records: dict) -> dict:
    """Numbers compared, each with its limit: ``{name: (value, limit)}``;
    a run is correct when every value is at most its limit."""
    lim = cell["check"]
    reqs = records["requests"]
    unfinished = sum(1 for r in reqs if r["last_s"] is None
                     or len(r["tokens"]) < r["max_new"])
    wrong_count = sum(1 for r in reqs if len(r["tokens"]) > r["max_new"])
    pick = _sample(records, seed, int(lim["sample_tokens"]))
    gaps = served_gaps(records, cfg, pick, pad_to=int(lim["pad_to"]))
    return {
        "logit_gap": (max(gaps) if gaps else float("inf"),
                      float(lim["logit_gap"])),
        "unfinished": (unfinished, 0),
        "over_budget": (wrong_count, 0),
        "tokens_short_of_sample": (
            max(0, int(lim["sample_tokens"])
                - sum(reqs[i]["max_new"] for i in pick)), 0),
    }


def control(cell: dict, cfg: dict, seed: int, records: dict) -> dict:
    """The compared number with the control in the program's place: at the
    same positions of the same sample, the gap of the token the fp8
    reference puts first."""
    lim = cell["check"]
    pick = _sample(records, seed, int(lim["sample_tokens"]))
    gaps = served_gaps(records, cfg, pick, pad_to=int(lim["pad_to"]),
                       control=True)
    return {"logit_gap": max(gaps) if gaps else float("nan")}
