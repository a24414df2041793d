"""Host-side span tracing: hierarchical spans into two sinks at once.

Each :meth:`SpanTracer.span` is both

* a ``jax.profiler.TraceAnnotation`` — the span lands on the host plane of
  any active profiler session (``jax.profiler.start_trace``, the launch
  CLIs' ``--jax-profile``), on the same clock as the device ops, so an idle
  stretch of the chip can be read by the host span open over it; TraceMe
  writes a span's ``args`` into its name (``serve.prefill#rid=5#``), so a
  reader matches on the part before ``#``;
* a Chrome trace-event ``"ph": "X"`` complete event kept in memory on
  ``time.perf_counter_ns`` (rebased to the tracer's construction, in
  microseconds), written as JSON by :meth:`SpanTracer.write` for
  Perfetto / chrome://tracing — the ``--trace-dir`` export.

Spans nest lexically via a context manager (``engine.eval >
engine.on_eval``); Perfetto renders the nesting from containment, so no
explicit parent ids are needed (everything runs on one host thread). The
engines never hold a span open across a ``yield``: a consumer's time between
two stream events belongs to no engine span.

:data:`SPANS` names every span the engines open and :data:`INSTANTS` every
zero-length marker; ``docs/observability.md`` gives each one's reader.

``jax_profile`` brackets a block with ``jax.profiler.start_trace`` /
``stop_trace`` into a directory TensorBoard/Perfetto can load — used by the
launch CLIs when ``--trace-dir`` is combined with ``--jax-profile``.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

# every span the engines open, in loop order (docs/observability.md)
SPANS = (
    "serve.admit",            # the round's gate; each request's admission
    "serve.prefill",          # one prefill (rid), up to its first token
    "serve.draft_prefill",    # the speculative draft's prefill (rid)
    "serve.block_budget",     # pre-chunk CoW forks, ensure, preemption
    "serve.decode_dispatch",  # the jitted decode-chunk call
    "serve.decode_wait",      # host reads of the chunk's outputs
    "serve.emit",             # record_decode, finish, stream deltas
    "engine.dispatch",        # key slice and the chunk (or step) call
    "engine.drain",           # in-scan metric drain (metrics on only)
    "engine.eval",            # evaluate, its host reads, rec.metrics
    "engine.on_eval",         # the user callback, inside engine.eval
)
INSTANTS = ("serve.submit", "serve.preempt", "serve.prefix_hit")


class SpanTracer:
    """Collects Chrome trace events; thread-safe appends, single process."""

    def __init__(self, process_name: str = "repro"):
        # perf_counter_ns at construction: an event's ts is microseconds
        # after it, which aligns the events with any perf_counter clock
        self.t0_ns = time.perf_counter_ns()
        self._lock = threading.Lock()
        self.events: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": process_name},
        }]

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self.t0_ns) / 1e3

    @contextmanager
    def span(self, name: str, **args):
        """Time a block as one complete ('X') event, and annotate it in the
        profiler trace; exceptions still close the span (the duration then
        covers up to the raise)."""
        t0 = self._now_us()
        try:
            with TraceAnnotation(name, **args):
                yield self
        finally:
            ev = {"name": name, "cat": "repro", "ph": "X", "ts": t0,
                  "dur": self._now_us() - t0, "pid": 0,
                  "tid": threading.get_ident() % 2 ** 31}
            if args:
                ev["args"] = dict(args)
            with self._lock:
                self.events.append(ev)

    def instant(self, name: str, **args):
        """A zero-duration marker (submissions, preemptions, prefix hits),
        also a zero-length annotation in the profiler trace."""
        with TraceAnnotation(name, **args):
            pass
        ev = {"name": name, "cat": "repro", "ph": "i", "ts": self._now_us(),
              "s": "t", "pid": 0, "tid": threading.get_ident() % 2 ** 31}
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self.events.append(ev)

    # -- export -------------------------------------------------------------

    def to_chrome_trace(self) -> dict:
        with self._lock:
            return {"traceEvents": list(self.events),
                    "displayTimeUnit": "ms"}

    def write(self, path: str) -> str:
        """Write the Chrome trace JSON; ``path`` may be a directory (then
        ``trace.json`` inside it). Returns the file path written."""
        if os.path.isdir(path) or path.endswith(os.sep):
            os.makedirs(path, exist_ok=True)
            path = os.path.join(path, "trace.json")
        else:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
            f.write("\n")
        return path


@contextmanager
def jax_profile(trace_dir: str):
    """Opt-in ``jax.profiler`` bracket around a block: the device trace
    into ``trace_dir``, with every SpanTracer span open inside it on the
    host plane."""
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
