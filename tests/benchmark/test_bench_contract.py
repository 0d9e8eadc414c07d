"""BENCHMARK.json against the contract it is written to, and against the files
it names: what the driver refuses before a single run must fail here first.
Every test takes the file through the `bench` fixture (conftest.py) and finds
an entry by its name, never by its place: test_bench_rehearsal.py runs them
again on a copy with a stand-in configuration, cell and metric appended."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check_source  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32 and all(line_ok(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(bench["configs"]) <= 24 and 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128


def test_command_names_only_files_under_paths(bench):
    for word in bench["command"][1:]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in bench["paths"]), word


def test_files_under_paths_have_plain_names(bench):
    for p in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line_ok(c["source"]) and line_ok(c["why"])
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        for k in c["reduced"]:  # never a width: a path named there, or one inside a group named there
            assert not check_source.is_width(k), k
        for path, value in bench_run.walk(body.get("published", {})).items():
            if check_source.is_width(path):
                assert check_source.same_json(check_source.lookup(body, path), value), path
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        assert body["program"]["engine"] in ("generation", "embedding")


def test_every_configuration_has_a_reference_that_covers_it(bench):
    """The module the file names (or benchmark/reference.py) is there, holds
    `check`, the forward its engine kind is compared through and that forward's
    tolerance with a value one can read, and `check` accepts the very
    `ModelConfig` the program would give the engine; the sizes in the file are
    that configuration's."""
    from benchmark import correctness
    from llm_mcp_tpu.models.configs import resolve_config

    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        name, module = bench_run.load_reference(body)
        assert name == body.get("reference", "reference")
        forward, tolerance = bench_run.REFERENCE_NEEDS[body["program"]["engine"]]
        assert callable(module.check) and callable(getattr(module, forward))
        assert 0.0 < float(getattr(module, tolerance)) < 0.5
        env = body["program"]["env"]
        model = env["TPU_MODEL" if body["program"]["engine"] == "generation" else "TPU_EMBED_MODEL"]
        model_cfg = resolve_config(model, "")
        module.check(model_cfg)
        bench_run.check_sizes(body, model_cfg, module)
        if body["program"]["engine"] == "generation":
            correctness.reference_request(body, int(env["TPU_MAX_SEQ_LEN"]))


def test_a_reference_module_that_lacks_its_tolerance_is_refused(tmp_path, monkeypatch):
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "half.py").write_text(
        "def check(cfg): pass\ndef logits(cfg, params, tokens, rows, cols): pass\n")
    monkeypatch.setattr(bench_run, "HERE", str(tmp_path))
    with pytest.raises(AttributeError, match="SERVED_TOL_REL"):
        bench_run.load_reference({"reference": "half", "program": {"engine": "generation"}})
    with pytest.raises(FileNotFoundError):
        bench_run.load_reference({"reference": "absent", "program": {"engine": "generation"}})


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and line_ok(w["why"])
        traffic = os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")
        assert os.path.isfile(traffic), traffic
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 4)
    # the two cells ISSUE 23 asked for first (its third could not be proved); where they stand is free
    assert {"decode_closed", "embed_batch"} <= set(names)


def test_metrics(bench):
    cells = [w["name"] for w in bench["workloads"]]
    every = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and line_ok(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):  # the metric it moves is reported wherever it is
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:  # setup_s, one more end-to-end metric and a layer metric in every cell
        assert sum(1 for m in bench["end_to_end"] if cell in m.get("workloads", cells)) >= 2
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_every_metric_has_a_reader_that_says_the_same(bench):
    for kind, key in (("end_to_end", "end_to_end"), ("layer_metrics", "per_layer")):
        for m in bench[key]:
            mod = bench_run.load_reader(kind, m["name"])
            assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE) == (
                m["name"], m["unit"], m["better"], m["source"])
            if key == "per_layer":
                assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
            assert callable(mod.read)


def test_layer_names_are_perf_md_layers(bench):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in perf, layer


def test_run_on_the_cpu_exits_non_zero_and_prints_no_result(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = next(w["name"] for w in bench["workloads"] if w["chips"] == 1)  # any: the refusal names the chips
    out = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload", cell,
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs 1 TPU chip" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
