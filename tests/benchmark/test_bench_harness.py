"""The harness is driven by data: a configuration, a cell and a layer metric
dropped in as new files are found without an edit; and one whole run at a tiny
size on the CPU, with the device check stubbed here in the test."""

import importlib.util
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TINY_GEN = {
    "name": "tiny-gen", "source": "test", "reduced": [],
    "hidden_size": 128, "num_hidden_layers": 2, "head_dim": 64,
    "program": {"engine": "generation",
                "env": {"TPU_MODEL": "tiny-qwen3", "TPU_MAX_SLOTS": 4, "TPU_MAX_SEQ_LEN": 256,
                        "TPU_QUANT": "", "TPU_KV_QUANT": ""},
                "expect": {"attn_impl": "xla"}},
}
TINY_TRAFFIC = {
    "endpoint": "chat", "loop": "closed", "clients": 3,
    "prompt_tokens": {"dist": "uniform", "lo": 16, "hi": 40},
    "max_tokens": {"dist": "const", "value": 12}, "temperature": 0.7, "stagger_first": True,
    "preroll_s": 1, "warmup_s": 1, "warmup_rounds_max": 2, "request_timeout_s": 60,
}
NEW_METRIC = '''"""A layer metric a later PR drops in as a file of its own."""
NAME, UNIT, BETTER, SOURCE = "requests_seen", "count", "higher", "program_counter"
LAYER, MOVES = "load generator", "out_tokens_per_s"


def read(run):
    return float(len(run["records"]))
'''


@pytest.fixture()
def checkout(tmp_path):
    """A copy of the benchmark with one configuration, one cell and one layer
    metric ADDED as files and entries; no file that was there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (root / "benchmark" / "configs" / "tiny-gen.json").write_text(json.dumps(TINY_GEN))
    (root / "benchmark" / "traffic" / "tiny_closed.json").write_text(json.dumps(TINY_TRAFFIC))
    (root / "benchmark" / "layer_metrics" / "requests_seen.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "tiny-gen", "source": "test", "reduced": [], "why": "test",
                             "file": "benchmark/configs/tiny-gen.json"})
    bench["workloads"].append({"name": "tiny.closed", "config": "tiny-gen", "traffic": "tiny_closed",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("itl_p95_ms", "out_tokens_per_s"):
            m["workloads"].append("tiny.closed")
    # an end-to-end metric whose reader is in the tree and which no cell carries yet
    bench["end_to_end"].insert(0, {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
                                   "bound": 0.1, "source": "host_clock",
                                   "workloads": ["tiny.closed"]})
    bench["per_layer"].append({"name": "requests_seen", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "load generator",
                               "moves": "out_tokens_per_s", "workloads": ["tiny.closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location("bench_run_copy", root / "benchmark" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return root, mod


def test_new_files_are_found_without_an_edit(checkout):
    root, run = checkout
    assert run.ROOT == str(root)
    spec = run.load_cell(run.ROOT, "tiny.closed")
    assert spec["config"]["program"]["env"]["TPU_MODEL"] == "tiny-qwen3"
    assert spec["traffic"]["clients"] == 3
    assert [m["name"] for m in spec["end_to_end"]] == [
        "ttft_p95_ms", "itl_p95_ms", "out_tokens_per_s", "setup_s"]
    assert [m["name"] for m in spec["per_layer"]] == ["requests_seen"]
    reader = run.load_reader("layer_metrics", "requests_seen")
    assert reader.read({"records": [1, 2, 3]}) == 3.0
    # and the cells that were there are untouched by the additions
    assert [m["name"] for m in run.load_cell(run.ROOT, "embed_batch")["end_to_end"]] == [
        "embeddings_per_s", "setup_s"]
    with pytest.raises(SystemExit):
        run.load_cell(run.ROOT, "no_such_cell")


def test_sizes_in_the_file_must_be_the_programs(checkout):
    _root, run = checkout
    from llm_mcp_tpu.models.configs import resolve_config

    cfg = resolve_config("tiny-qwen3", "")
    run.check_sizes(TINY_GEN, cfg)
    with pytest.raises(AssertionError, match="hidden_size"):
        run.check_sizes(dict(TINY_GEN, hidden_size=4096), cfg)


def test_one_whole_run_at_a_tiny_size(checkout, monkeypatch, capsys):
    root, run = checkout
    monkeypatch.setattr(run, "require_tpu",
                        lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setenv("TPU_WARMUP", "0")
    for key in TINY_GEN["program"]["env"]:
        monkeypatch.setenv(key, "")  # restored after the test: boot() writes them
    rc = run.main(["--workload", "tiny.closed", "--seed", "3000000001", "--seconds", "3", "--trace", "0"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "out_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"  # a CPU run never reads as a TPU's
    assert result["checks"]["served_tokens"] == 8 and result["checks"]["worst_regret_rel"] <= 0.05
    assert any(ln.startswith("itl_ms: median") for ln in out)
    assert any(ln.startswith("ttft_ms: median") for ln in out)
    assert not os.path.exists(root / ".bench_work" / f"tiny.closed.{os.getpid()}")
