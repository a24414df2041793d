"""``repro.obs`` — unified telemetry for the training Engine and serve stack.

Three layers, importable without pulling in the rest of the package:

* :mod:`repro.obs.metrics` — device-side :class:`MetricSpec`/:class:`MetricSet`
  accumulation carried inside the fused scan, drained at chunk boundaries.
* :mod:`repro.obs.tracing` — host-side :class:`SpanTracer` (spans into the
  profiler trace and Chrome trace-event JSON for Perfetto), the names in
  :data:`SPANS` / :data:`INSTANTS`, and the opt-in :func:`jax_profile` hook.
* :mod:`repro.obs.recorder` — :class:`Recorder`/:class:`NullRecorder`:
  counters, gauges, latency observations, histograms; JSONL event log,
  Prometheus text snapshot, in-process ``snapshot()``.
"""
from .metrics import (MetricSet, MetricSpec, staleness_hist_fn, tree_diff_l2,
                      tree_l2, trainer_metric_set)
from .recorder import NullRecorder, Recorder, cli_recorder
from .tap import make_tap
from .tracing import INSTANTS, SPANS, SpanTracer, jax_profile

__all__ = [
    "INSTANTS", "MetricSet", "MetricSpec", "NullRecorder", "Recorder",
    "SPANS", "SpanTracer", "cli_recorder", "jax_profile", "make_tap",
    "staleness_hist_fn", "trainer_metric_set", "tree_diff_l2", "tree_l2",
]
