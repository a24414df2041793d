#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell ``bench/workloads/<cell>.json`` names its configuration
(``bench/configs/<config>.json``) and its driver (``bench/drivers/<driver>.py``).
The driver builds the program's objects, warms up every shape the window
uses (that is set-up), runs the window and returns raw records; the metric
files in ``bench/metrics/`` turn records, and with ``--trace 1`` the
reduction of the profiler trace, into numbers. Then the driver's ``check``
compares what the timed path produced with a plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end ones with ``--trace 0``,
per-layer ones with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each compared number with its limit.
The same numbers close standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common  # noqa: E402


class NoChip(RuntimeError):
    pass


def _devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    return devs[:chips]


def _use_cache(cache_dir) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    # cache every program, however quick to build, so that set-up reads
    # the same programs from disk on every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def metric_files(bench_dir) -> list[pathlib.Path]:
    return sorted(pathlib.Path(bench_dir, "metrics").glob("*.py"))


def read_metrics(bench_dir, records: dict, red, kind: str) -> dict:
    """Every metric file of ``kind`` that finds something to read."""
    out = {}
    for path in metric_files(bench_dir):
        mod = common.load_module(path)
        if mod.KIND != kind:
            continue
        value = mod.read(records, red)
        if value is not None:
            out[path.name[:-3]] = {"value": value, "unit": mod.UNIT}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_dir=common.BENCH, require_chip: bool = True,
             cache_dir=common.CACHE_DIR, trace_dir=None, keep_trace=None,
             t_start: float = T_START) -> dict:
    """One run of one cell; returns the result object that is printed."""
    bench_dir = pathlib.Path(bench_dir)
    cell = common.load_json(bench_dir / "workloads" / f"{workload}.json")
    cell.setdefault("name", workload)
    cfg = common.load_config(bench_dir, cell["config"])
    devs = _devices(int(cell["chips"]), require_chip)
    _use_cache(cache_dir)
    driver = common.load_module(bench_dir / "drivers" / f"{cell['driver']}.py")
    ctx = common.Context(
        t_start=t_start, trace=trace,
        trace_dir=trace_dir or common.TRACE_DIR / f"{workload}.{seed}")
    records = driver.run(ctx, cell, cfg, seed, seconds)
    ctx.compiles.active = False
    ctx.end_gc_watch()
    records["gc_pauses"] = ctx.gc_pauses
    records.update(setup_s=ctx.setup_s, cfg=cfg, platform=devs[0].platform,
                   device_kind=devs[0].device_kind)
    records["compiles_in_window"] = len(ctx.compiles.events)
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    records["memory_limit"] = min(s.get("bytes_limit", 0) for s in stats)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": _count(),
              "memory_peak_bytes": int(peak)}

    red = None
    if trace:
        from bench import trace_reduce
        xplane = ctx.xplane()
        if xplane is not None:
            red = trace_reduce.reduce(xplane, window=common.TRACED,
                                      annotations=common.ANNOTATIONS,
                                      devices=len(devs))
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(xplane, os.path.join(
                    keep_trace, f"{workload}.{seed}.xplane.pb"))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
    metrics = read_metrics(bench_dir, records,
                           red, "per_layer" if trace else "end_to_end")
    checks = driver.check(cell, cfg, seed, records)
    correct = all(v <= lim for v, lim in checks.values())
    attempted, failed = driver_counts(records)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        out["breakdown"] = {"device_ops": red["device_ops"][:10],
                            "idle_gaps": red["idle_gaps"][:10]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    out["_notes"] = notes(records)
    return out


def _count() -> int:
    import jax
    return jax.device_count()


def driver_counts(records: dict) -> tuple[int, int]:
    if records["kind"] == "serve":
        reqs = records["requests"]
        failed = sum(1 for r in reqs if len(r["tokens"]) != r["max_new"])
        return len(reqs), failed
    return int(records["steps"]), 0


def notes(records: dict) -> list[str]:
    """Lines printed before the result: compiles in the window, and for
    open-loop traffic how late the generator ran."""
    lines = [f"compiles_in_window: {records['compiles_in_window']}",
             f"setup_s: {records['setup_s']!r}",
             f"window_s: {records['window_s']!r}",
             f"memory: bytes_limit {records['memory_limit']!r}"]
    gcs = records.get("gc_pauses", [])
    lines.append("gc_after_open: %d collections, %d of generation 2, "
                 "%r ms in all, longest %r ms" % (
                     len(gcs), sum(1 for g, _ in gcs if g == 2),
                     1e3 * sum(s for _, s in gcs),
                     1e3 * max((s for _, s in gcs), default=0.0)))
    if records["kind"] == "serve":
        late = [r["late_s"] for r in records["requests"]]
        lines.append(
            "generator_late_ms: p50 %r p95 %r max %r over %d requests" % (
                1e3 * common.percentile(late, 50),
                1e3 * common.percentile(late, 95), 1e3 * max(late),
                len(late)))
        lines.append("delivery_gaps_ms: longest five in the window %r" %
                     [round(1e3 * g, 1) for g in
                      delivery_gaps(records)[:5]])
        e = records["engine"]
        lines.append("engine: " + ", ".join(f"{k}={v!r}"
                                            for k, v in e.items()))
    return lines


def delivery_gaps(records: dict) -> list[float]:
    """Seconds between consecutive token deliveries in the window while a
    request was streaming, longest first: a host stall shows as a gap far
    over one decode chunk."""
    spans = [(r["first_s"], r["last_s"]) for r in records["requests"]
             if r["first_s"] is not None and r["last_s"] is not None]
    ts = sorted(t for t, *_ in records["deltas"] if t <= records["window_s"])
    return sorted((b - a for a, b in zip(ts, ts[1:])
                   if any(f <= a and b <= l for f, l in spans)),
                  reverse=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the raw .xplane.pb of a --trace 1 run here")
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    for line in out.pop("_notes"):
        print(line)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
