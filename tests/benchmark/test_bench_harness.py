"""The harness is driven by data: a configuration, a cell and a layer metric
dropped in as new files are found without an edit; and one whole run at a tiny
size on the CPU, with the device check stubbed here in the test."""

import dataclasses
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


# -- the traced slice brings its own counters (PR 47) ------------------------------


def test_the_traced_slice_reads_the_counters_at_its_own_edges_and_its_own_rounds(checkout, monkeypatch,
                                                                                   tmp_path):
    """`trace_slice` on the CPU with a stand-in engine (a traced run cannot be
    rehearsed whole here: its trace holds no device plane): the counters are
    read inside the slice at both edges, the ring's rounds are the slice's and
    the lead's, and `measure`'s `slice` is what `counters.slice_of` lays over
    the run."""
    import time
    from types import SimpleNamespace

    from benchmark import counters

    _root, run = checkout
    monkeypatch.setattr(run, "TRACE_SLICE_S", 0.4)
    calls = []

    def perf_stats():
        calls.append(time.monotonic())
        return {"phases": {"decode": {"samples": len(calls), "tokens": 256 * len(calls)}}}

    t0 = time.monotonic()
    ring = [{"etype": "decode", "fields": {"rid": 1, "rows": 64, "t": t0 - 5.0}},  # long before: not the slice's
            {"etype": "mixed", "fields": {"rid": 2, "rows": 61, "t": t0 + 0.02}},  # within the lead
            {"etype": "emit", "fields": {"rid": 2, "rows": 61, "delivered": 240, "t": t0 + 0.2}},
            {"etype": "decode", "fields": {"rid": 3, "rows": 32, "t": t0 + 0.3}},
            {"etype": "admit", "fields": None},
            {"etype": "decode", "fields": {"rid": 9, "rows": 7, "t": t0 + 60.0}}]  # after it
    gen = SimpleNamespace(perf_stats=perf_stats, _flight=SimpleNamespace(snapshot=lambda: ring))
    out = {}
    run.trace_slice(str(tmp_path / "trace"), t0 + 0.1, out, {"gen": gen})
    first, last, rounds = (out["cut"][k] for k in ("start", "end", "rounds"))
    assert out["start"] <= first["t"] < last["t"] <= out["stop"] <= out["written"]
    assert last["t"] - out["start"] == pytest.approx(0.4, abs=0.15)
    assert [e["perf"]["phases"]["decode"]["samples"] for e in (first, last)] == [1, 2]
    assert rounds == [("mixed", 61, t0 + 0.02), ("decode", 32, t0 + 0.3)]
    # an embedding cell has no engine loop: edges without counters, no rounds
    bare = {}
    run.trace_slice(str(tmp_path / "trace2"), time.monotonic(), bare, {"gen": None})
    assert bare["cut"]["rounds"] == [] and set(bare["cut"]["start"]) == set(bare["cut"]["end"]) == {"t"}
    # what a reader gets: the run with the slice laid over it, the window's own counters kept
    whole = {"start": {"perf": "w0"}, "end": {"perf": "w1"}, "window": (10.0, 50.0), "records": [],
             "slice": dict(out["cut"], window=(28.0, 28.4))}
    cut = counters.slice_of(whole)
    assert (cut["start"], cut["end"], cut["window"]) == (first, last, (28.0, 28.4))
    assert counters.delta(cut, "perf", "phases", "decode", "tokens") == 256.0
    assert counters.plain_rows(cut) == 32.0  # the plain round's, not the mixed round's 61
    assert whole["start"] == {"perf": "w0"} and counters.slice_of({"records": []}) is None


# -- a family brings a behaviour run.py has one value for (PR 47) ------------------

TABLE_MODULE = '''"""A reference module whose tables a case of the test below states."""
SERVED_TOL_REL = 0.1
HELD = {held}
ONLY = {only!r}
STATED = {stated!r}


def check(cfg):
    pass


def logits(cfg, params, tokens, rows, cols):
    raise NotImplementedError
'''


@pytest.mark.parametrize("held,only,stated,refused", [
    # a path of ONLY_VALUE in HELD: the family brings the behaviour and holds the key itself
    ('{"n_group": lambda c: 8, "topk_group": lambda c: 3}', {}, {}, None),
    ('{"moe_layer_freq": lambda c: [0, 1, 1]}', {}, {}, None),
    # the same path in ONLY or STATED is refused as any overlap with run.py's own tables is
    ("{}", {"n_group": 8}, {}, "ONLY holds ['n_group']"),
    ("{}", {}, {"topk_group": "nothing reads it"}, "STATED holds ['topk_group']"),
    ('{"n_group": lambda c: 8}', {"n_group": 8}, {}, "ONLY holds ['n_group']"),  # nor twice in the module
    # every other path of run.py's tables, in any table, as before this PR
    ('{"hidden_size": lambda c: c.dim}', {}, {}, "HELD holds ['hidden_size']"),
    ("{}", {"hidden_size": 128}, {}, "ONLY holds ['hidden_size']"),
    ("{}", {}, {"hidden_size": "a reason"}, "STATED holds ['hidden_size']"),
    ('{"rope_scaling.factor": lambda c: 1.0}', {}, {}, "HELD holds ['rope_scaling.factor']"),
    ('{"max_position_embeddings": lambda c: 512}', {}, {}, "HELD holds ['max_position_embeddings']"),
])
def test_a_module_may_hold_a_one_behaviour_key_in_held_and_only_there(checkout, held, only, stated, refused):
    root, run = checkout
    (root / "benchmark" / "references" / "case_tables.py").write_text(
        TABLE_MODULE.format(held=held, only=only, stated=stated))
    config = {"reference": "case_tables", "program": {"engine": "generation"}}
    if refused is None:
        name, mod = run.load_reference(config)
        assert name == "case_tables" and set(mod.HELD) <= set(run.ONLY_VALUE) <= run.own_paths()
    else:
        with pytest.raises(AssertionError) as err:
            run.load_reference(config)
        assert refused in str(err.value) and "a path is held once" in str(err.value)


V2_ROW = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "deepseek_v2_catalog_row.jsonl")


def v2_share_file() -> tuple[dict, dict]:
    """(the catalog's DeepSeek-V2 row, a configuration's file built from it): one
    chip of an 8-way expert group, cut as ISSUE 47 reckons it: a dense and seven
    expert layers, one routing group of 20 experts held, an eighth of the
    vocabulary; no width changed, the routing keys as published."""
    with open(V2_ROW) as f:
        row = json.loads(f.readline())
    cut = {"num_hidden_layers": 8, "n_routed_experts": 20, "vocab_size": 12_800}
    config = {"name": "deepseek-v2-ep8-bf16", "source": row["source_url"], "reference": "deepseek_v2",
              **json.loads(json.dumps(row["config"])), **cut,
              "reduced": sorted(cut), "published": {k: row["config"][k] for k in cut},
              "assumed": ["seeded random weights"], "deployment": "a test", "weights_seed": 0,
              "program": {"engine": "generation", "env": {"TPU_MODEL": "deepseek-v2-ep8"}}}
    return row, config


def v2_program(**more):
    """A `ModelConfig` at the file's sizes. `more` adds the fields a
    `model_config` PR would bring for routing in groups; without it, it is
    today's table, which has one behaviour."""
    from llm_mcp_tpu.models.configs import ModelConfig

    sizes = dict(
        name="deepseek-v2-ep8", arch="mla", vocab_size=12_800, dim=5120, n_layers=8, n_heads=128,
        n_kv_heads=1, ffn_hidden=12_288, norm_eps=1e-6, rope_theta=10_000.0, kv_lora_rank=512,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, n_experts=20,
        n_router_experts=160, experts_per_tok=6, n_shared_experts=2, moe_ffn_hidden=1536,
        first_dense_layers=1, norm_topk_prob=False, routed_scaling_factor=16.0, rope_factor=40.0,
        rope_orig_max=4096, rope_type="yarn", yarn_beta_fast=32, yarn_beta_slow=1, yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707)
    if not more:
        return ModelConfig(**sizes)
    grouped = dataclasses.make_dataclass(
        "GroupedConfig", [(k, type(v), v) for k, v in more.items()], bases=(ModelConfig,), frozen=True)
    return grouped(**sizes)


def test_the_catalogs_deepseek_v2_row_is_held_as_files_with_no_edit(checkout):
    """What stood between the row and a cell was run.py's `ONLY_VALUE`
    (`n_group` and `topk_group` at 1): the file must state 8 and 3 for the
    driver's comparison, and no module could take the paths over. Now the
    family's own reference holds them, and the file is refused by VALUE against
    a program with one behaviour, not by table."""
    from benchmark import check_source

    _root, run = checkout
    row, config = v2_share_file()
    assert check_source.differs(config, row) == []
    assert (config["n_group"], config["topk_group"], config["topk_method"], config["scoring_func"],
            config["seq_aux"], config["n_routed_experts"], config["published"]["n_routed_experts"]) == (
        8, 3, "group_limited_greedy", "softmax", True, 20, 160)
    name, module = run.load_reference(config)
    assert name == "deepseek_v2" and {"n_group", "topk_group"} <= set(module.HELD) & set(run.ONLY_VALUE)
    program = v2_program(n_group=8, topk_group=3)
    unheld = run.check_sizes(config, program, module)
    assert unheld == ["max_position_embeddings", "model_type", "seq_aux (" + module.STATED["seq_aux"] + ")"]
    module.check(program)
    # today's table has no field for the groups: one behaviour, and the value says so
    with pytest.raises(AssertionError, match="n_group=8 in the file, 1 in the program"):
        run.check_sizes(config, v2_program(), module)
    # a program that routes in other groups than the file states
    with pytest.raises(AssertionError, match="topk_group=3 in the file, 4 in the program"):
        run.check_sizes(config, v2_program(n_group=8, topk_group=4), module)
    # a router cut to the held experts is no share of the published one
    with pytest.raises(AssertionError, match="published.n_routed_experts=160 in the file, 20 in"):
        run.check_sizes(config, dataclasses.replace(program, n_router_experts=0), module)
    # under a module that holds the other keys and not the groups the path is run.py's again:
    # ONLY_VALUE stands, whatever fields the program has
    from types import SimpleNamespace

    others = SimpleNamespace(HELD={k: v for k, v in module.HELD.items() if k not in run.ONLY_VALUE},
                             STATED=module.STATED)
    with pytest.raises(AssertionError, match="n_group=8 in the file, 1 in the program"):
        run.check_sizes(config, program, others)
    # and a share that cuts a routing group is not what the reference computes
    with pytest.raises(NotImplementedError, match="not whole groups of 20"):
        module.check(dataclasses.replace(program, n_experts=30))
    with pytest.raises(NotImplementedError, match="softmax alone"):
        module.check(dataclasses.replace(program, router_score="sigmoid"))


def test_a_list_valued_layer_pattern_is_compared_element_by_element(checkout):
    """MiniMax-M3 states `moe_layer_freq` as a list, a layer each: held by a
    module's `HELD`, it is compared with what the program's layers are, element
    by element; with no holder it is refused against run.py's 1."""
    from types import SimpleNamespace

    from llm_mcp_tpu.models.configs import resolve_config

    _root, run = checkout
    cfg = resolve_config("tiny-v2", "")  # a dense layer, then two routed ones
    module = SimpleNamespace(HELD={"moe_layer_freq": lambda c: [0] * c.first_dense_layers
                                   + [1] * (c.n_layers - c.first_dense_layers)})
    listed = dict(TINY_V2, moe_layer_freq=[0, 1, 1])
    run.check_sizes(listed, cfg, module)
    run.check_sizes(dict(TINY_V2, moe_layer_freq=[0.0, 1, True]), cfg, module)  # JSON numbers, by value
    for wrong in ([0, 0, 1], [0, 1], [0, 1, 1, 1], 1):
        with pytest.raises(AssertionError, match="moe_layer_freq="):
            run.check_sizes(dict(TINY_V2, moe_layer_freq=wrong), cfg, module)
    with pytest.raises(AssertionError, match=r"moe_layer_freq=\[0, 1, 1\] in the file, 1 in the program"):
        run.check_sizes(listed, cfg)
