"""Run the four-worker training cell at the tiny size on four virtual CPU
devices, optionally with a fault planted, and print its result line.

    python tests/bench_chip/_four_workers.py [fault]

The device count must be forced before JAX starts, so this runs in a
process of its own."""

import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tiny import run_tiny  # noqa: E402


def main():
    import calibrate
    fault = sys.argv[1] if len(sys.argv) > 1 else ""
    with calibrate.fault(fault):
        result, checks = run_tiny("imnet1m-4w.train")
    print(json.dumps({"correct": result["correct"],
                      "count": result["device"]["count"],
                      "attempted": result["attempted"], "checks": checks}))


if __name__ == "__main__":
    main()
