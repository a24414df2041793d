#!/usr/bin/env python3
"""Readings that set a cell's limits and rate, in one process on the chip.
The benchmark's own runs never run this.

    python3 bench/calibrate.py limits --workload <cell> --seconds 10 \\
        --seeds 1,2,... [--control-seeds 1,2,3] [--faults half_batch,...]
    python3 bench/calibrate.py knee --workload <cell> --seconds 20 \\
        --rates 4,6,8,10

``limits`` prints, per seed, the numbers ``check`` compares for the
program, for the control in the program's place (``control`` of the cell's
driver), and for each planted fault (``bench/faults.py``); then, per number,
the largest program reading (the lower end of its limit) and the smallest
control and fault readings (the upper end).

``knee`` builds one serving engine, warms it once, and offers the cell's mix
at each rate in turn: for each it prints what was offered and finished, the
backlog when the window closed, and the latency tails.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common, faults  # noqa: E402
from bench import run as bench_run  # noqa: E402


def _load(workload: str):
    cell = common.load_json(common.BENCH / "workloads" / f"{workload}.json")
    cell.setdefault("name", workload)
    cfg = common.load_config(common.BENCH, cell["config"])
    driver = common.load_module(common.BENCH / "drivers"
                                / f"{cell['driver']}.py")
    return cell, cfg, driver


def _once(driver, cell, cfg, seed, seconds) -> dict:
    ctx = common.Context(t_start=time.perf_counter(), trace=False,
                         trace_dir=common.TRACE_DIR / "calibrate")
    rec = driver.run(ctx, cell, cfg, seed, seconds)
    ctx.compiles.active = False
    rec.update(cfg=cfg, compiles_in_window=len(ctx.compiles.events))
    return rec


def limits(args) -> None:
    cell, cfg, driver = _load(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in (args.control_seeds or "").split(",") if s}
    modes = [("program", None)] + [
        (f, faults.FAULTS[f]) for f in (args.faults or "").split(",") if f]
    rows = []
    for mode, fault in modes:
        for seed in seeds if fault is None else sorted(ctl):
            t = time.perf_counter()
            if fault is None:
                rec = _once(driver, cell, cfg, seed, args.seconds)
            else:
                with fault():
                    rec = _once(driver, cell, cfg, seed, args.seconds)
            got = {k: v for k, (v, _) in
                   driver.check(cell, cfg, seed, rec).items()}
            rows.append({"mode": mode, "seed": seed, **got})
            print(json.dumps(rows[-1]), flush=True)
            if fault is None and seed in ctl:
                rows.append({"mode": "control", "seed": seed,
                             **driver.control(cell, cfg, seed, rec)})
                print(json.dumps(rows[-1]), flush=True)
            print(f"# {mode} seed {seed}: {time.perf_counter() - t:.1f} s, "
                  f"compiles in window {rec['compiles_in_window']}",
                  flush=True)
            del rec
    names = [k for k in rows[0] if k not in ("mode", "seed")]
    summary = {}
    for k in names:
        prog = [r[k] for r in rows if r["mode"] == "program"]
        other = {m: min(r[k] for r in rows if r["mode"] == m and k in r)
                 for m in {r["mode"] for r in rows} - {"program"}
                 if any(k in r for r in rows if r["mode"] == m)}
        summary[k] = {"lower": max(prog), "others_min": other}
    print("SUMMARY " + json.dumps(summary), flush=True)


def knee(args) -> None:
    import jax
    from repro.serve import ServeEngine

    from bench import models, traffic
    cell, cfg, driver = _load(args.workload)
    model = models.of(cfg)
    mcfg = model.program_config(cfg)
    params = model.weights(cfg, 0)
    jax.block_until_ready(params)
    eng = ServeEngine(mcfg, params, **cell["engine"])
    driver._warm(eng, cell, mcfg.vocab)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell["traffic"], rate_per_s=rate)
        reqs = traffic.serve_requests(mix, 1000 + i, args.seconds, mcfg.vocab)
        ctx = common.Context(t_start=time.perf_counter(), trace=False,
                             trace_dir=common.TRACE_DIR / "calibrate")
        rec = driver._drive(ctx, eng, reqs, args.seconds, cell)
        rs = rec["requests"]
        done_in = sum(1 for r in rs if r["last_s"] is not None
                      and len(r["tokens"]) == r["max_new"]
                      and r["last_s"] <= args.seconds)
        backlog = sum(1 for r in rs if r["last_s"] is None
                      or r["last_s"] > args.seconds)
        ttft = [(r["first_s"] or rec["end_s"]) - r["due_s"] for r in rs]
        out_tok = sum(len(r["tokens"]) for r in rs)
        print(json.dumps({
            "rate": rate, "offered": len(rs), "done_in_window": done_in,
            "backlog_at_close": backlog, "drain_s": rec["end_s"] - args.seconds,
            "ttft_p50_ms": 1e3 * common.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * common.percentile(ttft, 95),
            "out_tokens_per_s": out_tok / rec["end_s"],
            "engine": rec["engine"]}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    lp = sub.add_parser("limits")
    lp.add_argument("--workload", required=True)
    lp.add_argument("--seconds", type=float, required=True)
    lp.add_argument("--seeds", required=True)
    lp.add_argument("--control-seeds", default="")
    lp.add_argument("--faults", default="")
    kp = sub.add_parser("knee")
    kp.add_argument("--workload", required=True)
    kp.add_argument("--seconds", type=float, required=True)
    kp.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench_run._devices(1, True)
    bench_run._use_cache(common.CACHE_DIR)
    (limits if args.cmd == "limits" else knee)(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
