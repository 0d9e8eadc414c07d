"""What PR 52 adds to the benchmark for the gated-short-convolution expert cell
`lfm2_decode_closed`: the catalog's `LFM2-8B-A1B` row through
`check_source.differs`, `load_reference` and `check_sizes` (as a row of
test_bench_config_tables.py's table, with the tables a module must bring, and as
the configuration's own file with the module that is there), `reduced` and
`published`, the byte functions against ISSUE 52's arithmetic and the parameter
count, the cell's traffic number for number with Granite's, and the four new
readers on a hand-made run: each gives its number from the expert counters and
the grouped kernels' names in the trace, and None (so no entry in the result
line) on a run without them, as the parent commit's runs and every other
configuration's are. Entries of BENCHMARK.json are found BY NAME."""

import copy
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import test_bench_config_tables as tables  # noqa: E402
from benchmark import check_source, counters, lfm2_bytes, solar_bytes  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from llm_mcp_tpu.models.configs import get_config  # noqa: E402

NEW = ["lfm2_round_roofline", "moe_grouped_ms", "moe_grouped_roofline", "moe_experts_touched_share"]
CELL = "lfm2_decode_closed"
CFG = get_config("lfm2-8b-a1b-d14")
FILE = os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b-d14-bf16.json")
ROW = json.load(open(os.path.join(HERE, "fixtures", "lfm2_catalog_row.json")))
EXPERT = 3 * 2048 * 1792 * 2  # one expert's three matrices, bfloat16


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


# -- the row as a row of test_bench_config_tables.py's table -------------------------

ROW_TABLES = {
    "HELD": {"norm_eps": "norm_eps", "num_experts": "n_experts", "num_dense_layers": "first_dense_layers",
             "layer_types": "layer_types", "conv_L_cache": "conv_taps", "use_expert_bias": "router_bias"},
    "ONLY": {"conv_bias": False},
    "STATED": {},
}


@pytest.fixture()
def row_in_the_table(monkeypatch):
    monkeypatch.setitem(tables.ROWS, ROW["name"], ROW)
    monkeypatch.setitem(tables.TABLES, ROW["name"], ROW_TABLES)
    return ROW["name"]


def test_the_catalog_row_is_held_whole_by_the_tables_a_module_brings(row_in_the_table, tmp_path, monkeypatch):
    name = row_in_the_table
    module = tables.load_module(tmp_path, monkeypatch, ROW_TABLES)
    config = tables.config_file(name)
    unheld = bench_run.check_sizes(config, tables.stub_program(name), module)
    assert unheld == ["max_position_embeddings", "model_type"]
    assert check_source.differs(config, ROW) == []
    # with no table brought, the run stops at exactly these paths
    with pytest.raises(AssertionError) as err:
        bench_run.check_sizes(config, tables.stub_program(name))
    paths = sorted(p for table in ROW_TABLES.values() for p in table)
    assert f"states {paths}, which check_sizes compares with nothing" in str(err.value)


@pytest.mark.parametrize("table,path", [(t, p) for t in ("HELD", "ONLY") for p in sorted(ROW_TABLES[t])])
def test_nothing_of_the_rows_tables_is_unread(row_in_the_table, table, path, tmp_path, monkeypatch):
    dropped = {t: {p: v for p, v in entries.items() if p != path} for t, entries in ROW_TABLES.items()}
    module = tables.load_module(tmp_path, monkeypatch, dropped)
    with pytest.raises(AssertionError) as err:
        bench_run.check_sizes(tables.config_file(row_in_the_table), tables.stub_program(row_in_the_table), module)
    assert f"states ['{path}'], which check_sizes compares with nothing" in str(err.value)


# -- the configuration's own file ------------------------------------------------------


def test_the_configurations_file_is_its_catalog_row_less_what_reduced_lists():
    config = json.load(open(FILE))
    assert check_source.differs(config, ROW) == [] and config["source"] == ROW["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["published"] == {"num_hidden_layers": 24, "layer_types": ROW["config"]["layer_types"]}
    assert config["num_hidden_layers"] == 14 and config["layer_types"] == ROW["config"]["layer_types"][:14]
    assert config["layer_types"] == ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3
    for key, value in ROW["config"].items():  # every other key letter for letter: no width, expert or row cut
        if key not in config["reduced"]:
            assert key in config and type(config[key]) is type(value) and config[key] == value, key
    assert not any(check_source.is_width(p) for p in config["reduced"])
    # a cut that `reduced` does not list, or a width, is refused
    assert check_source.differs(dict(config, num_experts=16), ROW)
    assert check_source.differs(dict(config, reduced=config["reduced"] + ["moe_intermediate_size"],
                                     moe_intermediate_size=896), ROW)
    name, module = bench_run.load_reference(config)
    assert name == "lfm2_moe"
    unheld = bench_run.check_sizes(config, CFG, module)
    assert [u.split(" ")[0] for u in unheld] == [
        "max_position_embeddings", "model_type", "published.layer_types", "published.num_hidden_layers"]
    module.check(CFG)
    module.check(get_config("tiny-lfm2"))
    for other in ("tiny-solar", "tiny-granite-hybrid", "tiny-kexaone", "tiny-llm"):
        with pytest.raises(NotImplementedError):
            module.check(get_config(other))
    import dataclasses

    for field, value in (("n_shared_experts", 1), ("router_score", "softmax"), ("n_router_experts", 64),
                         ("tie_embeddings", False), ("qk_norm", False), ("use_rope", False)):
        with pytest.raises(NotImplementedError):
            module.check(dataclasses.replace(CFG, **{field: value}))
    assert config["program"]["env"] == {"TPU_MODEL": "lfm2-8b-a1b-d14", "TPU_KV_QUANT": "int8",
                                        "TPU_MAX_SLOTS": 64, "TPU_MAX_SEQ_LEN": 1024}
    assert config["reference_request"] == {"prompt_bytes": 200, "tokens": 16} and config["weights_seed"] == 0
    expect = config["program"]["expect"]
    assert (expect["attn_impl"], expect["decode_impl"], expect["kv_quant"]) == ("pallas", "pallas", "int8")
    assert (expect["state_dtype"], expect["weights_dtype"], expect["expert_dtype"]) == ("bfloat16",) * 3
    assert expect["ragged_prefill"] is False and expect["spec_enabled"] is False and expect["_pool"] is None
    said = " ".join(config["assumed"])
    for word in ("ONE table", "head_dim 64", "BEFORE rotation", "NO epsilon", "deviation 0.01", "taps",
                 "byte tokenizer", "64 slots x 1024", "tail"):
        assert word in said, word
    assert "4,667,077,376" in config["deployment"] and "two-stage pipeline" in config["deployment"]


@pytest.mark.parametrize("path,moved", [
    ("layer_types", ["conv"] * 14), ("num_hidden_layers", 24), ("conv_L_cache", 4), ("conv_bias", True),
    ("norm_eps", 1e-6), ("num_experts", 16), ("num_dense_layers", 1), ("use_expert_bias", False),
    ("num_experts_per_tok", 2), ("moe_intermediate_size", 896), ("intermediate_size", 4096),
    ("hidden_size", 1024), ("num_key_value_heads", 4), ("rope_theta", 10_000), ("vocab_size", 32_768),
    ("routed_scaling_factor", 2.5), ("norm_topk_prob", False),
])
def test_a_key_of_the_file_that_is_not_the_programs_stops_the_run(path, moved):
    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    config[path] = moved
    with pytest.raises(AssertionError, match=path):
        bench_run.check_sizes(config, CFG, module)


def test_the_tables_name_every_key_run_py_does_not_hold():
    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    model = set(bench_run.model_paths(config))
    assert model - bench_run.own_paths() == set(module.HELD) | set(module.ONLY)
    assert set(module.HELD) == {"norm_eps", "num_experts", "num_dense_layers", "layer_types",
                                "conv_L_cache", "use_expert_bias"}
    assert module.ONLY == {"conv_bias": False}
    assert set(module.STATED) == {"published.num_hidden_layers", "published.layer_types"}
    assert module.CONTROLS == ("fp8", "lost_tail", "no_gate", "bias_weighs")
    assert 0.05 < module.SERVED_TOL_REL < 0.6


# -- the byte functions --------------------------------------------------------------


def test_the_byte_functions_are_issue_52s_arithmetic():
    assert CFG.param_count() == 4_667_077_376 and round(CFG.param_count() * 2 / 1e9, 2) == 9.33
    assert lfm2_bytes.conv_layers(CFG) == 11 and CFG.n_attn_layers == 3
    assert lfm2_bytes.tail_step_bytes(CFG, 1) == 2 * 11 * 2 * 2048 * 2  # read and written
    assert 11 * 2 * 2048 * 2 == 90_112  # "a row is 90 KB"
    assert round(64 * 90_112 / 1e6, 1) == 5.8  # "tails 5.8 MB"
    assert solar_bytes.kv_row_bytes(CFG, "int8") == 3 * 8 * 2 * (64 + 2) == 3168  # "3.1 KB a token"
    assert round(64 * 1024 * 3168 / 1e9, 1) == 0.2
    assert EXPERT * 32 * 12 == 2 * 4_227_858_432  # the banks: 8.46 GB of the 9.33
    assert round(EXPERT * 32 * 12 / 1e9, 2) == 8.46
    # 11.4 ms a step at 819 GB/s with every expert touched
    assert round(CFG.param_count() * 2 / 819e9 * 1e3, 1) == 11.4
    # the chance that 64 rows x 4 choices miss one of 32 experts: 0.0003 by independent pairs
    assert 1e-4 < (1 - 1 / 32) ** 256 < 5e-4


def lfm2_run(kernels=("grouped_swiglu", "grouped_down"), touched=32.0) -> dict:
    """Counters at both edges (100 decode steps a layer of 60 rows, 240 pairs,
    `touched` experts), a trace with 10 runs of the decode program of 50 ms,
    each holding 48 calls of either grouped kernel of 0.5 and 0.3 ms (the slice's
    edges cut the first and the last: eight whole runs), no request in flight
    (no KV to count), and the traced slice: of the rounds dispatched in it the
    plain ones carry 30 rows, and its 40 steps a layer 120 pairs."""
    E, D, F, Le = 32, 2048, 1792, 12
    params = {"embed": np.zeros((64, 8), np.int16), "final_norm": np.zeros((8,), np.int16),
              "first": [{"w1": np.zeros((8, 16), np.int16)}, {"w1": np.zeros((8, 16), np.int16)}],
              "layers": {"router": np.zeros((Le, 8, E), np.int16),
                         "w1e": np.zeros((Le, E, 4, 6), np.int16), "w3e": np.zeros((Le, E, 4, 6), np.int16),
                         "w2e": np.zeros((Le, E, 6, 4), np.int16)},
              "gqa": {"wq": np.zeros((3, 8, 8), np.int16)}, "conv": {"w_in": np.zeros((9, 8, 24), np.int16)}}
    gen = SimpleNamespace(cfg=CFG, params=params, kv_quant="int8", decode_chunk=4, max_slots=64)

    def book(steps, rows, pairs):
        counts = [[steps * rows, steps * pairs, steps * touched, steps * 12, steps] for _ in range(Le)]
        return {"experts": {"counts": [counts, [[0] * 5] * Le], "held": E, "router": E}}

    ops, mods = [], []
    for r in range(10):
        t0 = r * 60e6
        mods.append(("jit_decode_chunk_fn(77)", t0, t0 + 50e6))
        for c in range(48):
            a = t0 + 1e6 + c * 1e6
            ops.append((f"%{kernels[0]}.{c} = bf16[256,1792] custom-call(...)", a, a + 0.5e6))
            ops.append((f"%{kernels[1]}.{c} = f32[256,2048] custom-call(...)", a + 0.5e6, a + 0.8e6))
    ops.append((f"%{kernels[0]}.999 = bf16[256,1792] custom-call(...)", 650e6, 651e6))  # outside any run
    return {"sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"},
            "start": {"perf": book(0, 60, 240)}, "end": {"perf": book(100, 60, 240)},
            "records": [], "window": (10.0, 50.0),
            "slice": {"start": {"perf": book(30, 60, 240)},
                      "end": {"perf": {"experts": {
                          "counts": [[[30 * 60 + 40 * 30, 30 * 240 + 40 * 120, 30 * touched + 40 * touched,
                                       70 * 12, 70]] * Le, [[0] * 5] * Le], "held": E, "router": E}}},
                      "window": (26.0, 34.0), "window_abs": (126.0, 134.0),
                      "rounds": [("decode", 30, 126.5), ("mixed", 64, 128.0), ("decode", 30, 130.0)]},
            "trace_reduced": {"module_runs": {"jit_decode_chunk_fn": [10, 0.050]},
                              "whole_runs": {"jit_decode_chunk_fn": [8, 0.050]}},
            "_planes": ([(0, ops, mods)], {})}


def test_each_new_reader_gives_its_number_on_a_run_with_the_counters_and_the_kernels(capsys):
    run = lfm2_run()
    got = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    cut = counters.slice_of(run)
    gen = run["sut"]["gen"]
    one = lfm2_bytes.one_expert_bytes(gen)
    assert one == 3 * 4 * 6 * 2 and lfm2_bytes.bank_bytes(gen) == 12 * 32 * one
    assert got["moe_experts_touched_share"] == pytest.approx(100.0)
    assert got["moe_grouped_ms"] == pytest.approx(48 * 0.8)  # the stray call outside a run is not read
    # the slice's own steps: 12 layers x 32 touched banks, 120 pairs a layer in and out
    need = 12 * 32 * one + 12 * 120 * 2048 * (2 + 4)
    assert lfm2_bytes.grouped_step_bytes(cut) == pytest.approx(need)
    assert got["moe_grouped_roofline"] == pytest.approx(100 * 4 * need / 819e9 / 38.4e-3)
    assert 0 < got["moe_grouped_roofline"] < 100
    assert lfm2_bytes.grouped_tile_flops(cut) == pytest.approx(12 * 32 * 128 * 6 * 2048 * 1792)
    assert "of the bfloat16 peak" in capsys.readouterr().out
    weights = 2 * (64 * 8 + 8 + 2 * 8 * 16 + 12 * 8 * 32 + 3 * 8 * 8 + 9 * 8 * 24)  # the tied table ONCE
    step = lfm2_bytes.decode_step_bytes(cut)
    assert step == pytest.approx(weights + 12 * 32 * one + lfm2_bytes.tail_step_bytes(CFG, 30))  # no KV yet
    assert got["lfm2_round_roofline"] == pytest.approx(100 * 4 * step / 819e9 / 0.050)
    assert 0 < got["lfm2_round_roofline"] < 100
    half = lfm2_run(touched=16.0)
    assert reader("moe_experts_touched_share").read(half) == pytest.approx(50.0)
    assert lfm2_bytes.decode_step_bytes(counters.slice_of(half)) == pytest.approx(step - 12 * 16 * one)


def test_the_weights_a_step_reads_are_the_parameter_count():
    """With every expert touched, the weights' part of `decode_step_bytes` is
    every parameter once: the byte functions count nothing twice and leave
    nothing out (the tiny preset's own tree, float32)."""
    import jax
    import jax.numpy as jnp

    from benchmark import peaks
    from llm_mcp_tpu.models.llama import init_llama_params

    cfg = get_config("tiny-lfm2")
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    gen = SimpleNamespace(cfg=cfg, params=params)
    Le = cfg.n_layers - cfg.first_dense_layers
    assert lfm2_bytes.is_ours(gen) and lfm2_bytes.one_expert_bytes(gen) == 3 * 128 * 64 * 4
    whole = (peaks.decode_weight_bytes(params) - lfm2_bytes.bank_bytes(gen)
             + Le * cfg.n_experts * lfm2_bytes.one_expert_bytes(gen))
    assert whole == 4 * cfg.param_count()
    assert lfm2_bytes.tail_step_bytes(cfg, 2, 4) == 8 * 2 * 2 * 2 * 128 * 4


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_gives_nothing_where_the_program_lacks_what_it_reads(name):
    """The parent commit given this cell's files (no such layer kind in its
    table), Solar's cell (grouped kernels and counters, but its own layers),
    a dense cell, a bare run, and a window without a decode step."""
    solar = lfm2_run()
    solar["sut"]["gen"].cfg = get_config("solar-open2-250b-ep8")
    if name != "moe_experts_touched_share":  # a counter any expert configuration has
        assert reader(name).read(solar) is None
    parent = lfm2_run()
    parent["sut"]["gen"].cfg = SimpleNamespace(name="x", recurrent=True, n_experts=32, n_layers=14)
    if name != "moe_experts_touched_share":
        assert reader(name).read(parent) is None
    dense = lfm2_run(kernels=("decode_attn_q8_blocked", "append_kv_q8"))
    dense["sut"]["gen"].cfg = get_config("qwen3-8b")
    for edge in (dense["start"], dense["end"], dense["slice"]["start"], dense["slice"]["end"]):
        edge["perf"] = {}
    assert reader(name).read(dense) is None
    bare = {"sut": {"gen": lfm2_run()["sut"]["gen"]}, "start": {}, "end": {}, "records": [],
            "window": (0.0, 1.0), "device": {"kind": "TPU v5 lite"}}
    assert reader(name).read(bare) is None
    idle = lfm2_run()
    idle["end"] = idle["start"]  # a window without a decode step
    idle["slice"]["end"] = idle["slice"]["start"]
    if name != "moe_grouped_ms":
        assert reader(name).read(idle) is None
    unnamed = lfm2_run(kernels=("fusion", "custom-call"))  # a program whose kernels go by other names
    if name in ("moe_grouped_ms", "moe_grouped_roofline"):
        assert reader(name).read(unnamed) is None


ON_CELL = {*NEW, "decode_occupancy", "decode_round_ms", "engine_itl_p95_ms", "window_compiles.serve",
           "pallas_busy_share", "decode_token_yield", "engine_host_ms_per_round",
           "engine_event_gap_p95_ms", "stream_write_lag_p95_ms", "decode_attn_ms",
           "setup_first_dispatch_s.serve", "setup_first_dispatch_s.trace_lower",
           "setup_first_dispatch_s.backend", "state_pool_share", "event_gap_admit_share",
           "slot_vacant_ms", "slot_vacant_queued_ms", "moe_local_pairs_per_row", "moe_load_max_over_mean"}


def test_the_cell_is_granites_traffic_number_for_number_and_its_entries_are_found_by_name(bench):
    traffic = os.path.join(ROOT, "benchmark", "traffic")
    mine = json.load(open(os.path.join(traffic, CELL + ".json")))
    assert mine == json.load(open(os.path.join(traffic, "granite_decode_closed.json")))
    assert (mine["loop"], mine["clients"], mine["temperature"], mine["stagger_first"]) == ("closed", 64, 0.7, True)
    assert mine["prompt_tokens"] == {"dist": "uniform", "lo": 64, "hi": 128}
    assert mine["max_tokens"] == {"dist": "const", "value": 512} and mine["request_timeout_s"] == 120
    assert (mine["preroll_s"], mine["warmup_s"], mine["warmup_rounds_max"]) == (10, 4, 4)
    assert mine["warmup_rounds"] == [{"max_tokens": {"dist": "const", "value": 48}}]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-8b-a1b-d14-bf16", CELL, 1)
    assert sum(w["config"] == cell["config"] for w in bench["workloads"]) == 1  # one cell on it
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"] == json.load(open(FILE))["reduced"]
    assert config["file"] == os.path.relpath(FILE, ROOT) and config["source"] == ROW["source_url"]
    reports = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"itl_p95_ms", "out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in bench["per_layer"]}
    on_cell = {n for n, m in layer.items() if CELL in m.get("workloads", [CELL])}
    assert on_cell >= ON_CELL, ON_CELL - on_cell  # a later metric may list the cell too
    for m in (*bench["end_to_end"], *bench["per_layer"]):  # appended to a list, never put first
        cells = m.get("workloads", [])
        if CELL in cells and "granite_decode_closed" in cells:
            assert cells.index(CELL) > cells.index("granite_decode_closed")
    for name in NEW:  # its own entries, each on this cell alone
        assert layer[name]["workloads"] == [CELL] and layer[name]["moves"] == "out_tokens_per_s"
        mod = reader(name)
        assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            name, layer[name]["unit"], layer[name]["better"], layer[name]["source"],
            layer[name]["layer"], "out_tokens_per_s")
    # no other cell's kernel or roofline metrics were put on this one
    for other in ("ssd_decode_ms", "ssd_decode_roofline", "granite_round_roofline", "gdn_decode_ms",
                  "kda_decode_ms", "solar_round_roofline", "kexaone_round_roofline", "win_attn_ms",
                  "decode_round_roofline", "decode_attn_roofline", "decode_copy_ms", "admit_program_share"):
        assert CELL not in layer[other]["workloads"]
