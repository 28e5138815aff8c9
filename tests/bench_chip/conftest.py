"""Shared pieces of the chip benchmark's CPU tests.

The benchmark's harness lives in ``benchmarks/chip``; ``tiny.py`` puts it
on the path and runs its cells at a tiny size on the CPU.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tiny import BENCH, ROOT  # noqa: E402,F401


@pytest.fixture
def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
