"""Two-worker trainer run under the JAX profiler, in a subprocess with two
forced host devices (the main pytest process keeps its one device).

    python tests/_profiler_spans_check.py <trace dir>

Invoked by tests/test_profiler_spans.py, which reads the trace it writes.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=2 "
    + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_profiler_spans import profiled, train_tiny  # noqa: E402


def main():
    assert jax.device_count() == 2, jax.device_count()
    with profiled(sys.argv[1]):
        train_tiny(n_workers=2)


if __name__ == "__main__":
    main()
