"""The one way a test under tests/benchmark reads BENCHMARK.json: the `bench`
fixture, a fresh copy a test. A test that holds something of the file takes
`bench` as its argument, finds its own entries BY NAME, and says nothing of
another configuration's entries or of what comes last in a list: a later PR
appends a configuration, a cell and metrics at the ends of the lists, and
test_bench_rehearsal.py hands every such test a copy with stand-ins appended."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture()
def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
