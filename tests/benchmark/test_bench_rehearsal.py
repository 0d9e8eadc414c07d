"""A rehearsal of the next PR that brings a model: to a copy of BENCHMARK.json
it appends, each at its list's end, a stand-in configuration, a stand-in cell
on it and a stand-in per-layer metric that lists EVERY cell, puts the cell on
the metrics a generation cell shares, and runs every test of this directory
that holds something of the file (one that takes the `bench` fixture alone)
on that copy. A test that finds an entry by its place, holds what comes last,
or holds a cell's set of metrics to an exact set fails here, in the PR that
writes it, and not in the later PR that may not edit it (PR 37 could add none
of its seven metrics for four such pins). The pins as PR 35 wrote them are kept
below as the control: each fails on the copy."""

import copy
import glob
import importlib
import inspect
import json
import os
import re
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

CONFIG, CELL, METRIC = "standin-config", "standin_cell", "standin_metric"
CONFIG_FILE = "tests/benchmark/fixtures/standin-config.json"  # qwen3-8b-int8.json's sizes under another name
# its child process reads the file on disk, whatever the test is handed
ON_DISK = {"test_run_on_the_cpu_exits_non_zero_and_prints_no_result"}


def holders():
    """(module.test, function) of every test of this directory whose one
    argument is `bench`."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "test_bench_*.py"))):
        if os.path.samefile(path, __file__):
            continue
        name = os.path.basename(path)[:-3]
        mod = importlib.import_module(name)
        for fn_name, fn in sorted(vars(mod).items()):
            if fn_name.startswith("test_") and inspect.isfunction(fn) and fn_name not in ON_DISK \
                    and list(inspect.signature(fn).parameters) == ["bench"]:
                out.append(pytest.param(fn, id=f"{name}.{fn_name}"))
    return out


def appended(bench: dict) -> dict:
    """The file as a `model_config` PR for a generation model leaves it."""
    bench = copy.deepcopy(bench)
    with open(os.path.join(ROOT, CONFIG_FILE)) as f:
        source = json.load(f)["source"]
    generation = set(next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")["workloads"])
    cells = [w["name"] for w in bench["workloads"]] + [CELL]
    bench["configs"].append({"name": CONFIG, "source": source, "file": CONFIG_FILE, "reduced": [],
                             "why": "a stand-in: the next configuration a PR appends"})
    bench["workloads"].append({"name": CELL, "config": CONFIG, "traffic": "decode_closed", "chips": 1,
                               "why": "a stand-in: the next cell a PR appends, on the configuration it brings"})
    for m in bench["end_to_end"] + bench["per_layer"]:  # the cell reports what every generation cell reports
        if generation <= set(m.get("workloads", [])):
            m["workloads"].append(CELL)
    bench["per_layer"].append({"name": METRIC, "unit": "count", "better": "lower", "source": "program_counter",
                               "layer": "step programs", "moves": "setup_s", "workloads": cells})
    return bench


@pytest.fixture()
def rehearsed(bench, monkeypatch):
    """The copy, and a stand-in reader where the harness looks for the stand-in
    metric's (a real PR brings `benchmark/layer_metrics/<metric>.py`)."""
    find = bench_run.load_reader
    standin = types.SimpleNamespace(NAME=METRIC, UNIT="count", BETTER="lower", SOURCE="program_counter",
                                    LAYER="step programs", MOVES="setup_s", read=lambda run: None)
    monkeypatch.setattr(bench_run, "load_reader",
                        lambda kind, name: standin if (kind, name) == ("layer_metrics", METRIC) else find(kind, name))
    return appended(bench)


def test_the_copy_ends_in_the_stand_ins_and_keeps_every_entry_that_was_there(bench, rehearsed):
    for key in ("configs", "workloads", "per_layer"):
        assert [e["name"] for e in rehearsed[key]][:-1] == [e["name"] for e in bench[key]]
    assert [rehearsed[k][-1]["name"] for k in ("configs", "workloads", "per_layer")] == [CONFIG, CELL, METRIC]
    assert rehearsed["per_layer"][-1]["workloads"] == [w["name"] for w in rehearsed["workloads"]]
    on_cell = {m["name"] for m in rehearsed["end_to_end"] + rehearsed["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"itl_p95_ms", "out_tokens_per_s", "setup_s", "decode_round_ms", METRIC} <= on_cell


@pytest.mark.parametrize("holder", holders())
def test_a_test_that_holds_the_file_still_passes_with_entries_appended(holder, rehearsed):
    holder(rehearsed)


BY_PLACE = re.compile(r'\[\s*"(?:configs|workloads|end_to_end|per_layer)"\s*\]\s*\[\s*[-0-9:]')


def test_no_test_here_finds_an_entry_of_the_file_by_its_place():
    """`bench["workloads"][-1]`, `bench["per_layer"][-3:]` and their like; this
    file alone may, for the control below."""
    for path in sorted(glob.glob(os.path.join(HERE, "*.py"))):
        if os.path.samefile(path, __file__):
            continue
        with open(path) as f:
            found = [(n, ln.strip()) for n, ln in enumerate(f, 1) if BY_PLACE.search(ln)]
        assert not found, (os.path.basename(path), found)
    assert BY_PLACE.search('cell = bench["workloads"][-1]') and BY_PLACE.search('bench[ "per_layer" ] [-3:]')
    assert not BY_PLACE.search('layer[name]["workloads"] == [CELL]')


OLMO_CELL, OLMO_NEW = "olmo_hybrid_decode_closed", ["gdn_decode_ms", "gdn_decode_roofline", "olmo_round_roofline"]
PR35_PINS = {  # tests/benchmark/test_bench_olmo_hybrid.py:228-246 at 3305593, line for line
    "the_last_cell": lambda b: b["workloads"][-1]["name"] == OLMO_CELL,
    "the_last_configuration": lambda b: b["configs"][-1]["reduced"] == ["layer_types", "num_hidden_layers"],
    "the_last_three_metrics": lambda b: [m["name"] for m in b["per_layer"][-3:]] == OLMO_NEW,
    "exactly_seventeen_on_the_cell": lambda b: len(
        {m["name"] for m in b["per_layer"] if OLMO_CELL in m["workloads"]}) == 17,
}


@pytest.mark.parametrize("pin", sorted(PR35_PINS))
def test_a_pin_by_place_or_by_exact_set_fails_the_rehearsal(pin, rehearsed):
    assert not PR35_PINS[pin](rehearsed)
