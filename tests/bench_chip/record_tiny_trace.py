"""Record the small TPU trace that ``test_trace.py`` reads:

    python3 tests/bench_chip/record_tiny_trace.py <out.xplane.pb>

Under the JAX profiler (its Python tracer off, as in the harness) and
inside the harness's window annotation, it runs a jitted matmul program
three times, waiting for each, with a host sleep of 50 ms annotated
``host_sleep`` before each run and after the last. So the window holds
three runs of the program ``jit_tiny_step`` and four idle stretches of
about 50 ms that the host spent in ``host_sleep``: the first and last
leave room for the millisecond by which the trace's device and host
clocks disagree. Run it on a TPU; it copies the profiler's
``.xplane.pb`` to the path given.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "benchmarks", "chip"))

from harness.trace import WINDOW  # noqa: E402


@jax.jit
def tiny_step(a, b):
    return jnp.tanh(a @ b) @ b


def main() -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_tiny_trace.py: no TPU")
    a = jnp.ones((2048, 2048), jnp.float32)
    b = jnp.eye(2048, dtype=jnp.float32) * 0.5
    tiny_step(a, b).block_until_ready()          # compile outside the trace
    out_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW):
        for i in range(3):
            with jax.profiler.TraceAnnotation("host_sleep"):
                time.sleep(0.05)
            tiny_step(a, b).block_until_ready()
        with jax.profiler.TraceAnnotation("host_sleep"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, sys.argv[1])
    shutil.rmtree(out_dir)
    print(f"{sys.argv[1]}: {os.path.getsize(sys.argv[1])} bytes")


if __name__ == "__main__":
    main()
