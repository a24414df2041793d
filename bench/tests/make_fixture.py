"""Record the small trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/make_fixture.py <out.xplane.pb>   # on one TPU chip

Inside the traced window (the ``bench.traced`` host span) the host runs
three jitted matrix products, each under ``bench.submit``, with sleeps of
20 ms under ``bench.wait`` between them, so the device is busy three times
and idle in between while the host waits.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.traced"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.submit"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.02)
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0],
                    out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
