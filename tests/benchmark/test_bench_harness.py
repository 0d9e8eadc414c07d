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
TINY_V2 = {  # another architecture, brought as files: its reference module is named here
    "name": "tiny-v2-lat", "source": "test", "reduced": [], "reference": "deepseek_v2",
    "reference_request": {"prompt_bytes": 150, "tokens": 6},
    "hidden_size": 128, "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 256, "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "n_routed_experts": 4, "num_experts_per_tok": 2,
    "n_shared_experts": 2, "moe_intermediate_size": 64, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1.0,
    "norm_topk_prob": False, "tie_word_embeddings": True, "max_position_embeddings": 512,
    "rope_scaling": {"type": "yarn", "factor": 4, "original_max_position_embeddings": 64,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
    "program": {"engine": "generation",
                "env": {"TPU_MODEL": "tiny-v2", "TPU_MAX_SLOTS": 4, "TPU_MAX_SEQ_LEN": 512,
                        "TPU_QUANT": "", "TPU_KV_QUANT": ""},
                "expect": {"attn_impl": "xla"}},
}
# Four keys of a catalog row (Kimi-K2.5) that run.py's own tables do not know, and
# the module a `model_config` PR would add to hold them: the DeepSeek-V2
# reference as it stands, with the tables beside it.
KIMI_KEYS = {"encoder_no_repeat_ngram_size": 0, "ep_size": 1, "num_nextn_predict_layers": 0,
             "top_k": 50}
KIMI_MODULE = '''"""references/deepseek_v2.py, and the tables for four more keys of the file."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "deepseek_v2_wrapped", os.path.join(os.path.dirname(os.path.abspath(__file__)), "deepseek_v2.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
check, logits, SERVED_TOL_REL = _base.check, _base.logits, _base.SERVED_TOL_REL

ONLY = {"encoder_no_repeat_ngram_size": 0, "ep_size": 1, "num_nextn_predict_layers": 0}
STATED = {"top_k": "the sampler's default; a request states its own"}
'''
# Under the load of the other test workers a first dispatch takes seconds, and one
# inside a 3 s window left no request due in it (no `ttft_p95_ms`) or no delta
# at all. So nothing here is left to compile in the window: one prompt length
# (one prefill bucket), and warm-up rounds shaped to admit 1, 2 and 4 prompts at
# once (the admit program pads a batch to a power of two) before the mix's own
# rounds run until one builds nothing; and the pre-roll takes the clients'
# first, simultaneous requests.
TINY_TRAFFIC = {
    "endpoint": "chat", "loop": "closed", "clients": 3,
    "prompt_tokens": {"dist": "const", "value": 24},
    "max_tokens": {"dist": "const", "value": 12}, "temperature": 0.7, "stagger_first": True,
    "preroll_s": 2, "warmup_s": 1, "warmup_rounds": [{"clients": 1}, {"clients": 2}, {"clients": 4}],
    "warmup_rounds_max": 6, "request_timeout_s": 60,
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
    (root / "benchmark" / "configs" / "tiny-v2-lat.json").write_text(json.dumps(TINY_V2))
    no_key = {k: v for k, v in TINY_V2.items() if k != "reference"}
    (root / "benchmark" / "configs" / "tiny-v2-nokey.json").write_text(
        json.dumps(dict(no_key, name="tiny-v2-nokey")))
    (root / "benchmark" / "configs" / "tiny-v2-keys.json").write_text(
        json.dumps(dict(TINY_V2, **KIMI_KEYS, name="tiny-v2-keys", reference="v2_keys")))
    (root / "benchmark" / "configs" / "tiny-v2-keys-unheld.json").write_text(
        json.dumps(dict(TINY_V2, **KIMI_KEYS, name="tiny-v2-keys-unheld")))
    (root / "benchmark" / "references" / "v2_keys.py").write_text(KIMI_MODULE)
    (root / "benchmark" / "traffic" / "tiny_closed.json").write_text(json.dumps(TINY_TRAFFIC))
    (root / "benchmark" / "layer_metrics" / "requests_seen.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "tiny-gen", "source": "test", "reduced": [], "why": "test",
                             "file": "benchmark/configs/tiny-gen.json"})
    bench["workloads"].append({"name": "tiny.closed", "config": "tiny-gen", "traffic": "tiny_closed",
                               "chips": 1, "why": "test"})
    for name in ("tiny-v2-lat", "tiny-v2-nokey", "tiny-v2-keys", "tiny-v2-keys-unheld"):
        bench["configs"].append({"name": name, "source": "test", "reduced": [], "why": "test",
                                 "file": f"benchmark/configs/{name}.json"})
        bench["workloads"].append({"name": name + ".closed", "config": name, "chips": 1,
                                   "traffic": "tiny_closed", "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("itl_p95_ms", "out_tokens_per_s"):
            m["workloads"] += ["tiny.closed", "tiny-v2-lat.closed", "tiny-v2-nokey.closed"]
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
    stub_device(run, monkeypatch, TINY_GEN)
    rc = run.main(["--workload", "tiny.closed", "--seed", "3000000001", "--seconds", "6", "--trace", "0"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "out_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"  # a CPU run never reads as a TPU's
    assert result["checks"]["served_tokens"] == 8 and result["checks"]["worst_regret_rel"] <= 0.05
    assert result["checks"]["reference"] == "reference" and result["checks"]["tolerance"] == 0.08
    assert any(ln.startswith("itl_ms: median") for ln in out)
    assert any(ln.startswith("ttft_ms: median") for ln in out)
    assert not os.path.exists(root / ".bench_work" / f"tiny.closed.{os.getpid()}")


# -- a configuration of another architecture brings its own reference -----------


def stub_device(run, monkeypatch, config):
    monkeypatch.setattr(run, "require_tpu",
                        lambda chips: {"platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setenv("TPU_WARMUP", "0")
    for key in config["program"]["env"]:
        monkeypatch.setenv(key, "")  # restored after the test: boot() writes them


def test_a_configuration_that_names_its_reference_runs_to_correct(checkout, monkeypatch, capsys):
    """tiny-v2 (latent attention, routed and shared experts, yarn) through the
    whole path, with nothing edited but added files. The engine's prefill runs
    at the program's default `capacity_factor`; at these lengths no expert
    overflows (tests/benchmark/test_bench_reference.py shows where one does)."""
    _root, run = checkout
    stub_device(run, monkeypatch, TINY_V2)
    rc = run.main(["--workload", "tiny-v2-lat.closed", "--seed", "3000000002", "--seconds", "3",
                   "--trace", "0"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True and result["failed"] == 0
    checks = result["checks"]
    assert checks["reference"] == "deepseek_v2" and checks["tolerance"] == 0.12
    assert checks["served_tokens"] == 6  # the file's reference_request
    assert checks["prompt_tokens"] > 128  # it crosses two 64-token KV blocks
    assert checks["worst_regret_rel"] <= 0.01  # float32 weights on the CPU
    assert any(ln.startswith("reference: deepseek_v2") for ln in out)


def test_without_the_key_it_fails_before_the_engine_is_built(checkout, monkeypatch):
    _root, run = checkout
    stub_device(run, monkeypatch, TINY_V2)
    import llm_mcp_tpu.executor as executor

    def no_engine(*a, **k):
        raise AssertionError("the engine was built before the reference was asked")

    monkeypatch.setattr(executor, "GenerationEngine", no_engine)
    with pytest.raises(NotImplementedError, match="no plain reference for 'tiny-v2'"):
        run.main(["--workload", "tiny-v2-nokey.closed", "--seed", "3000000003", "--seconds", "3",
                  "--trace", "0"])


def test_a_configuration_whose_module_brings_the_tables_runs_to_correct(checkout, monkeypatch, capsys):
    """The `tiny-v2` file with four more keys of a catalog row, and a module
    that wraps references/deepseek_v2.py and adds `ONLY` and `STATED` for them:
    the whole path to `correct`, with nothing edited but added files."""
    _root, run = checkout
    stub_device(run, monkeypatch, TINY_V2)
    rc = run.main(["--workload", "tiny-v2-keys.closed", "--seed", "3000000004", "--seconds", "3",
                   "--trace", "0"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["checks"]["reference"] == "v2_keys" and result["checks"]["tolerance"] == 0.12
    stated = [ln for ln in out if ln.startswith("stated in the file and held to nothing: ")]
    assert len(stated) == 1  # once a run, where a reviewer reads it
    assert stated[0].endswith(
        "max_position_embeddings; top_k (the sampler's default; a request states its own)")


def test_without_the_tables_it_stops_before_the_engine_is_built(checkout, monkeypatch):
    _root, run = checkout
    stub_device(run, monkeypatch, TINY_V2)
    import llm_mcp_tpu.executor as executor

    def no_engine(*a, **k):
        raise AssertionError("the engine was built before the file's keys were held")

    monkeypatch.setattr(executor, "GenerationEngine", no_engine)
    with pytest.raises(AssertionError) as err:
        run.main(["--workload", "tiny-v2-keys-unheld.closed", "--seed", "3000000005",
                  "--seconds", "3", "--trace", "0"])
    assert f"states {sorted(KIMI_KEYS)}, which check_sizes compares with nothing" in str(err.value)


@pytest.mark.parametrize("key,value,says", [
    ("kv_lora_rank", 256, "kv_lora_rank=256"),
    ("moe_intermediate_size", 32, "moe_intermediate_size=32"),
    ("n_routed_experts", 8, "n_routed_experts=8"),
    ("num_key_value_heads", 1, "num_key_value_heads=1"),  # the published files state the heads
    ("n_group", 2, "n_group=2"),  # group-limited routing: the program has none
    ("q_lora_rank", 16, "q_lora_rank=16"),
    ("rope_scaling", dict(TINY_V2["rope_scaling"], factor=8), "rope_scaling.factor=8"),
    ("rope_scaling", None, "rope_scaling.factor=1.0"),
    ("n_future_width", 7, "states ['n_future_width']"),  # a number nobody compares
])
def test_every_size_a_file_states_is_held(checkout, key, value, says):
    _root, run = checkout
    from llm_mcp_tpu.models.configs import resolve_config

    cfg = resolve_config("tiny-v2", "")
    run.check_sizes(TINY_V2, cfg)
    with pytest.raises(AssertionError) as err:
        run.check_sizes(dict(TINY_V2, **{key: value}), cfg)
    assert says in str(err.value)


def test_a_reference_request_must_fit_the_positions_served(checkout):
    from benchmark import correctness

    assert correctness.reference_request({}, 2048) == (72, 8)
    assert correctness.reference_request(TINY_V2, 512) == (150, 6)
    with pytest.raises(AssertionError, match="does not fit"):
        correctness.reference_request(TINY_V2, 256)
    with pytest.raises(AssertionError, match="holds"):
        correctness.reference_request({"reference_request": {"prompt_bytes": 9, "rows": 1}}, 512)
