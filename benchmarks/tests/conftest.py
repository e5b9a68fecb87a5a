"""Tests of the benchmark's own code: ``python -m pytest benchmarks/tests``
(CPU). They rehearse the command at 16 brokers / 512 partitions; nothing
here is a device number."""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = os.path.join(BENCH, "tests", "BENCHMARK.tiny.json")


@pytest.fixture(scope="session")
def benchmark_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def tiny():
    with open(TINY) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def cpu_device():
    import run
    return run.device_report(1, rehearse=True)
