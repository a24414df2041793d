import os
import sys

# tests see exactly ONE device (the dry-run sets its own 512-device flag in a
# subprocess); keep XLA quiet and deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_default_prng_impl", "threefry2x32")


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches():
    """Free compiled executables between test modules — a single pytest
    process otherwise accumulates enough XLA CPU JIT state to abort."""
    yield
    jax.clear_caches()


@pytest.fixture
def profiled_spans(tmp_path):
    """``run(fn) -> (fn(), spans)``: ``fn`` under ``jax.profiler``, with the
    spans of the profiler trace's host plane whose names are in
    ``repro.obs.SPANS``, as sorted ``(name, start_ns, end_ns)``. TraceMe may
    write a span's args after a ``#`` in its name."""
    from repro.obs import SPANS

    def run(fn):
        jax.profiler.start_trace(str(tmp_path))
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path, = tmp_path.glob("**/*.xplane.pb")
        pd = jax.profiler.ProfileData.from_file(str(path))
        spans = sorted(
            (ev.name.split("#")[0], ev.start_ns, ev.start_ns + ev.duration_ns)
            for pl in pd.planes if pl.name.startswith("/host:")
            for ln in pl.lines for ev in ln.events
            if ev.name.split("#")[0] in SPANS)
        return out, spans

    return run
