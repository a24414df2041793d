"""Shared pieces of the harness: where files live and how they load, the run
context (window, compile count, trace), and the statistics metrics use.

Everything that belongs to one cell, configuration or per-layer metric is a
file of its own, found by name:

* ``bench/workloads/<cell>.json``  — the cell: config, driver, traffic, check
* ``bench/configs/<config>.json``  — the configuration as it is run
* ``bench/models/<model_type>.py`` — a serving architecture's program
  config, weights, reference and counts (``bench/models/__init__.py``)
* ``bench/drivers/<driver>.py``    — ``run(ctx, cell, cfg, seed, seconds)``
  and ``check(cell, cfg, seed, records)``
* ``bench/metrics/<metric>.py``    — ``KIND``, ``UNIT``, ``read(records, red)``
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import pathlib
import shutil
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed path inside the checkout: the directory is part of each cache key
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"

# host annotations the harness writes around its calls into each layer
ANNOTATIONS = ("bench.submit", "bench.stream_next", "bench.wait",
               "bench.engine_run", "bench.eval")
TRACED = "bench.traced"   # spans the traced window on the host
# where a loaded configuration came from, so that its model module is
# looked up beside it (``bench.models.of``)
BENCH_DIR_KEY = "_bench_dir"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(bench_dir, name: str) -> dict:
    """``bench_dir/configs/<name>.json``, marked with ``bench_dir``."""
    cfg = load_json(pathlib.Path(bench_dir) / "configs" / f"{name}.json")
    cfg[BENCH_DIR_KEY] = str(bench_dir)
    return cfg


def load_module(path, name: str | None = None):
    """Import a file by path (metric files carry dots in their names)."""
    path = pathlib.Path(path)
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def percentile(values, q: float) -> float:
    """Linear-interpolated q-th percentile (0 <= q <= 100) of all values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def annotate(name: str):
    """A host span in the profiler's trace (no cost worth naming when off)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts the programs JAX builds (XLA compiles, and loads from the
    persistent cache) while ``active``. One listener per process."""

    _instance = None

    def __init__(self):
        self.active = False
        self.events: list[str] = []

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax
            inst = cls()

            def on_duration(event, duration, **kw):
                if inst.active and (
                        event.endswith("backend_compile_duration")
                        or event.endswith("cache_retrieval_time_sec")):
                    inst.events.append(event)

            jax.monitoring.register_event_duration_secs_listener(on_duration)
            cls._instance = inst
        return cls._instance


class Context:
    """What a driver needs from the harness: the clock the window and
    set-up are read on, the compile count, and the profiler trace."""

    def __init__(self, *, t_start: float, trace: bool, trace_dir):
        self.t_start = t_start
        self.trace = trace
        self.trace_dir = pathlib.Path(trace_dir)
        self.t_open = self.t_close = None
        self.trace_t0 = self.trace_t1 = None
        self.compiles = CompileCounter.get()
        self._traced = None
        # (generation, seconds) of each garbage collection from the
        # window's opening until the driver returns
        self.gc_pauses: list[tuple[int, float]] = []
        self._gc_t = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc_pauses.append((info["generation"],
                                   time.perf_counter() - self._gc_t))
            self._gc_t = None

    def open_window(self) -> float:
        self.compiles.events.clear()
        self.compiles.active = True
        gc.callbacks.append(self._on_gc)
        self.t_open = time.perf_counter()
        return self.t_open

    def end_gc_watch(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def close_window(self) -> float:
        """Mark the end of the window; compiles are counted on until the
        driver returns, drain included."""
        self.t_close = time.perf_counter()
        return self.t_close

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_start

    @property
    def tracing(self) -> bool:
        return self._traced is not None

    def start_trace(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.trace_dir))
        self._traced = annotate(TRACED)
        self._traced.__enter__()
        self.trace_t0 = time.perf_counter()

    def stop_trace(self) -> None:
        import jax
        self.trace_t1 = time.perf_counter()
        self._traced.__exit__(None, None, None)
        self._traced = None
        jax.profiler.stop_trace()

    def xplane(self) -> pathlib.Path | None:
        found = sorted(self.trace_dir.glob("**/*.xplane.pb"))
        return found[-1] if found else None
