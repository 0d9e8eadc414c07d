"""What PR 57 adds to the benchmark for the latent-attention expert-share cell
`joyai_decode_closed`: the catalog's `JoyAI-LLM-Flash` row through
`check_source.differs`, `load_reference` and `check_sizes` (the configuration's own
file with the module that is there), `reduced` and `published`, the byte functions
against ISSUE 57's arithmetic and the parameter count, the cell's traffic number
for number with LFM2's, and the three new readers on a hand-made run: each gives
its number from the expert counters, the program's count of latent positions and
the latent attention kernel's name in the trace, and None (so no entry in the
result line) on a run without them, as the parent commit's runs and every other
configuration's are. Entries of BENCHMARK.json are found BY NAME."""

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

from benchmark import check_source, counters, joyai_bytes, peaks  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from llm_mcp_tpu.models.configs import get_config  # noqa: E402

NEW = ["joyai_round_roofline", "mla_attn_roofline", "latent_cache_bytes_share"]
CELL = "joyai_decode_closed"
CFG = get_config("joyai-llm-flash-ep16")
FILE = os.path.join(ROOT, "benchmark", "configs", "joyai-llm-flash-ep16-bf16.json")
ROW = json.load(open(os.path.join(HERE, "fixtures", "joyai_catalog_row.json")))
EXPERT = 3 * 2048 * 768 * 2  # one expert's three matrices, bfloat16


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


# -- the configuration's own file ------------------------------------------------------


def test_the_configurations_file_is_its_catalog_row_less_what_reduced_lists():
    config = json.load(open(FILE))
    assert check_source.differs(config, ROW) == [] and config["source"] == ROW["source_url"]
    assert config["reduced"] == ["n_routed_experts"] and config["published"] == {"n_routed_experts": 256}
    assert config["n_routed_experts"] == 16 and ROW["config"]["n_routed_experts"] == 256
    for key, value in ROW["config"].items():  # every other key letter for letter: no width, layer or row cut
        if key not in config["reduced"]:
            assert key in config and type(config[key]) is type(value) and config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"], config["num_experts_per_tok"]) == (40, 129280, 8)
    assert not any(check_source.is_width(p) for p in config["reduced"])
    # a cut that `reduced` does not list, or a width, is refused
    assert check_source.differs(dict(config, num_hidden_layers=20), ROW)
    assert check_source.differs(dict(config, reduced=config["reduced"] + ["q_lora_rank"], q_lora_rank=768), ROW)
    assert check_source.differs(dict(config, published={"n_routed_experts": 128}), ROW)
    name, module = bench_run.load_reference(config)
    assert name == "joyai_flash"
    unheld = bench_run.check_sizes(config, CFG, module)
    assert [u.split(" ")[0] for u in unheld] == ["ep_size", "max_position_embeddings", "model_type"]
    module.check(CFG)
    module.check(get_config("tiny-joyai"))
    for other in ("tiny-solar", "tiny-kexaone", "tiny-lfm2", "tiny-llm", "tiny-v2", "tiny-mla"):
        with pytest.raises(NotImplementedError):
            module.check(get_config(other))
    assert config["program"]["env"] == {"TPU_MODEL": "joyai-llm-flash-ep16", "TPU_KV_QUANT": "int8",
                                        "TPU_MAX_SLOTS": 64, "TPU_MAX_SEQ_LEN": 1024}
    assert config["reference_request"] == {"prompt_bytes": 200, "tokens": 16} and config["weights_seed"] == 0
    expect = config["program"]["expect"]
    assert (expect["attn_impl"], expect["decode_impl"], expect["kv_quant"]) == ("pallas", "pallas", "int8")
    assert (expect["weights_dtype"], expect["expert_dtype"]) == ("bfloat16",) * 2
    assert expect["ragged_prefill"] is True and expect["spec_enabled"] is True
    assert expect["_prefix_budget"] == 0 and expect["_pool"] is None and expect["_migrate_in"] is None
    said = " ".join(config["assumed"])
    for word in ("deviation 0.01", "NO epsilon", "byte tokenizer", "64 slots x 1024", "rope_interleave",
                 "580 bytes", "not held by the engine", "COUNTED_OFF", "never rides"):
        assert word in said, word
    assert "4,776,521,472" in config["deployment"] and "16-chip" in config["deployment"]
    assert "2 rows a step where the deployment's sees 32" in config["deployment"]


@pytest.mark.parametrize("path,moved", [
    ("num_hidden_layers", 20), ("q_lora_rank", 0), ("kv_lora_rank", 256), ("qk_head_dim", 128),
    ("qk_rope_head_dim", 32), ("v_head_dim", 64), ("n_routed_experts", 256), ("n_shared_experts", 2),
    ("num_experts_per_tok", 4), ("moe_intermediate_size", 1536), ("intermediate_size", 4096),
    ("hidden_size", 1024), ("num_key_value_heads", 8), ("head_dim", 128), ("rope_theta", 10_000),
    ("vocab_size", 32_768), ("routed_scaling_factor", 1.0), ("norm_topk_prob", False),
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("rope_interleave", False),
    ("num_nextn_predict_layers", 0), ("n_group", 8), ("topk_group", 4), ("moe_layer_freq", 2),
    ("first_k_dense_replace", 3), ("tie_word_embeddings", True), ("rms_norm_eps", 1e-5),
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
    assert model - bench_run.own_paths() == (set(module.HELD) | set(module.ONLY) | set(module.STATED)) - {
        "published.n_routed_experts"}
    assert set(module.HELD) == {"scoring_func", "num_nextn_predict_layers", "qk_head_dim",
                                "published.n_routed_experts"}
    assert module.ONLY == {"topk_method": "noaux_tc", "rope_interleave": True}
    assert set(module.STATED) == {"ep_size"} and "placement" in module.STATED["ep_size"]
    # n_group, topk_group and moe_layer_freq stand on run.py's ONLY_VALUE; head_dim and
    # num_key_value_heads on its own tables
    assert {"n_group", "topk_group", "moe_layer_freq"} <= set(bench_run.ONLY_VALUE)
    assert bench_run.DERIVED_KEYS["num_key_value_heads"](CFG) == 32 and CFG.resolved_head_dim == 64
    assert module.CONTROLS == ("bf16", "int8_latent", "fp8", "no_scale", "rope_halves")
    assert 0.05 < module.SERVED_TOL_REL < 0.6


# -- the byte functions --------------------------------------------------------------


def test_the_byte_functions_are_issue_57s_arithmetic():
    assert CFG.param_count() == 4_776_521_472 and round(CFG.param_count() * 2 / 1e9, 2) == 9.55
    assert joyai_bytes.latent_row_bytes(CFG, "int8") == 40 * 580 == 23_200  # "23.2 KB a token"
    assert joyai_bytes.latent_row_bytes(CFG, "") == 40 * 576 * 2
    assert round(64 * 1024 * 23_200 / 1e9, 2) == 1.52  # "64 slots x 1024 positions = 1.52 GB"
    assert round(24 * 8192 * 23_200 / 1e9, 1) == 4.6  # the long-context cell of PERF.md section 7
    assert EXPERT * 16 * 39 == 2 * 2_944_401_408 and round(EXPERT * 16 * 39 / 1e9, 2) == 5.89  # the banks
    # 64 rows x 8 choices x 16/256 = 32 pairs a layer on 16 experts: 87% of the banks touched
    assert 64 * 8 * 16 // 256 == 32 and round(1 - (1 - 1 / 16) ** 32, 2) == 0.87
    assert round(0.87 * EXPERT * 16 * 39 / 1e9, 1) == 5.1  # "5.1 GB"
    attn = 2 * (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    assert round(40 * attn / 1e9, 1) == 2.1  # "latent-attention weights 2.1 GB"
    assert round(64 * 230 * 23_200 / 1e9, 2) == 0.34  # "latent rows at a mean fill of 230 positions"
    assert round(8.35e9 / 819e9 * 1e3, 1) == 10.2  # "8.35 GB a step, 10.2 ms at 819 GB/s"


def joyai_run(kernel="decode_attn_mla_q8_whole", touched=14.0) -> dict:
    """Counters at both edges (100 decode steps a layer of 60 rows, 30 pairs,
    `touched` experts of 16; the latent stream's book: 100 steps of 60 rows at 200
    positions), a trace with 10 runs of the decode program of 50 ms, each holding
    160 calls of the latent attention kernel of 0.05 ms (the slice's edges cut the
    first and the last: eight whole runs), and the traced slice: of the rounds
    dispatched in it the plain ones carry 30 rows, its 40 steps a layer 15 pairs,
    and 30 rows at 300 positions."""
    E, Le = 16, 39
    params = {"embed": np.zeros((64, 8), np.int16), "lm_head": np.zeros((8, 64), np.int16),
              "final_norm": np.zeros((8,), np.int16),
              "dense_layers": {"w1": np.zeros((1, 8, 16), np.int16), "w_uq": np.zeros((1, 6, 12), np.int16)},
              "layers": {"router": np.zeros((Le, 8, 256), np.int16), "w_uq": np.zeros((Le, 6, 12), np.int16),
                         "w1e": np.zeros((Le, E, 4, 6), np.int16), "w3e": np.zeros((Le, E, 4, 6), np.int16),
                         "w2e": np.zeros((Le, E, 6, 4), np.int16), "w1s": np.zeros((Le, 4, 6), np.int16)}}
    gen = SimpleNamespace(cfg=CFG, params=params, kv_quant="int8", decode_chunk=4, max_slots=64,
                          max_seq_len=1024)

    def book(steps, rows, pairs, positions):
        counts = [[steps * rows, steps * pairs, steps * touched, steps * 4, steps] for _ in range(Le)]
        return {"experts": {"counts": [counts, [[0] * 5] * Le], "held": E, "router": 256},
                "decode_attn": {"steps": steps, "tokens_live": steps * rows * positions,
                                "tokens_streamed": steps * 64 * 1024, "block_tokens": 0}}

    ops, mods = [], []
    for r in range(10):
        t0 = r * 60e6
        mods.append(("jit_decode_chunk_fn(77)", t0, t0 + 50e6))
        for c in range(160):
            a = t0 + 1e6 + c * 0.2e6
            ops.append((f"%{kernel}.{c} = bf16[64,32,512] custom-call(...)", a, a + 0.05e6))
            ops.append((f"%grouped_swiglu.{c} = bf16[128,768] custom-call(...)", a + 0.05e6, a + 0.15e6))
    ops.append((f"%{kernel}.999 = bf16[64,32,512] custom-call(...)", 650e6, 651e6))  # outside any run
    last = book(30, 60, 30, 200)
    more = book(40, 30, 15, 300)
    end = {"experts": {"counts": [[[a + b for a, b in zip(x, y)] for x, y in zip(
                last["experts"]["counts"][0], more["experts"]["counts"][0])], [[0] * 5] * Le],
                       "held": E, "router": 256},
           "decode_attn": {k: last["decode_attn"][k] + more["decode_attn"][k] for k in last["decode_attn"]}}
    return {"sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"},
            "start": {"perf": book(0, 60, 30, 200)}, "end": {"perf": book(100, 60, 30, 200)},
            "records": [], "window": (10.0, 50.0),
            "slice": {"start": {"perf": last}, "end": {"perf": end},
                      "window": (26.0, 34.0), "window_abs": (126.0, 134.0),
                      "rounds": [("decode", 30, 126.5), ("decode", 30, 130.0)]},
            "trace_reduced": {"module_runs": {"jit_decode_chunk_fn": [10, 0.050]},
                              "whole_runs": {"jit_decode_chunk_fn": [8, 0.050]}},
            "_planes": ([(0, ops, mods)], {})}


def test_each_new_reader_gives_its_number_on_a_run_with_the_counters_and_the_kernel(capsys):
    run = joyai_run()
    got = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    cut = counters.slice_of(run)
    gen = run["sut"]["gen"]
    one = joyai_bytes.one_expert_bytes(gen)
    assert one == 3 * 4 * 6 * 2 and joyai_bytes.bank_bytes(gen) == 39 * 16 * one
    # the table is left out (a row a sequence), the head is read
    rest = peaks.decode_weight_bytes(gen.params) - joyai_bytes.bank_bytes(gen)
    assert rest == 2 * (8 * 64 + 8 + 8 * 16 + 6 * 12 + 39 * (8 * 256 + 6 * 12 + 4 * 6))
    # the slice's own steps: 30 rows at 300 positions; the window's: 60 rows at 200
    assert joyai_bytes.latent_positions_a_step(cut) == pytest.approx(30 * 300)
    assert joyai_bytes.latent_positions_a_step(run) == pytest.approx(60 * 200)
    assert joyai_bytes.latent_step_bytes(cut) == pytest.approx(30 * 300 * 23_200)
    step = joyai_bytes.decode_step_bytes(cut)
    assert step == pytest.approx(rest + 39 * 14 * one + 30 * 300 * 23_200 + 30 * 23_200)
    assert got["joyai_round_roofline"] == pytest.approx(100 * 4 * step / 819e9 / 0.050)
    assert 0 < got["joyai_round_roofline"] < 100
    # the kernel: 160 calls of 0.05 ms a round (the stray call outside a run is not read)
    assert joyai_bytes.kernel_round_s(run) == pytest.approx(160 * 0.05e-3)
    assert joyai_bytes.kernel_round_s(run, joyai_bytes.GROUPED) == pytest.approx(160 * 0.1e-3)
    need = 30 * 300 * 23_200 + 40 * 30 * 32 * (2 * 512 + 64) * 2
    assert joyai_bytes.attn_step_bytes(cut) == pytest.approx(need)
    ops = 40 * 30 * 300 * 32 * 2 * (2 * 512 + 64)
    assert joyai_bytes.attn_step_ops(cut) == pytest.approx(ops)
    assert need / 819e9 > ops / 393e12  # bound by bytes at this fill
    assert got["mla_attn_roofline"] == pytest.approx(100 * 4 * need / 819e9 / 8e-3)
    assert 0 < got["mla_attn_roofline"] < 100
    out = capsys.readouterr().out
    assert "live positions 13.7% of the cache's" in out and "latent attention 8.00 ms a round" in out
    # the window's share: 60 rows at 200 positions over everything a step of the window moves
    whole = rest + 39 * 14 * one + 60 * 200 * 23_200 + 60 * 23_200
    assert got["latent_cache_bytes_share"] == pytest.approx(100 * 60 * 200 * 23_200 / whole)
    fewer = joyai_run(touched=7.0)
    assert joyai_bytes.decode_step_bytes(counters.slice_of(fewer)) == pytest.approx(step - 39 * 7 * one)


def test_the_weights_a_step_reads_are_the_parameter_count_less_the_table():
    """With every held expert touched, the weights' part of `decode_step_bytes`
    is every parameter but the embedding table once: the byte functions count
    nothing twice and leave nothing out (the tiny preset's own tree, float32)."""
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.models.llama import init_llama_params

    cfg = get_config("tiny-joyai")
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    gen = SimpleNamespace(cfg=cfg, params=params)
    Le = cfg.n_layers - cfg.first_dense_layers
    assert joyai_bytes.is_ours(gen) and joyai_bytes.one_expert_bytes(gen) == 3 * 64 * 32 * 4
    whole = (peaks.decode_weight_bytes(params) - joyai_bytes.bank_bytes(gen)
             + Le * cfg.n_experts * joyai_bytes.one_expert_bytes(gen))
    assert whole == 4 * (cfg.param_count() - cfg.vocab_size * cfg.dim)
    assert joyai_bytes.latent_row_bytes(cfg, "int8") == 4 * (32 + 16 + 4)


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_gives_nothing_where_the_program_lacks_what_it_reads(name):
    """The parent commit given this cell's files (its table has no such preset and
    its engine no count of latent positions), the other expert cells (counters and
    grouped kernels, but their own layers), a dense cell, a bare run, a window
    without a decode step, and a program whose kernel goes by another name."""
    for other in ("k-exaone-236b-ep8", "lfm2-8b-a1b-d14", "solar-open2-250b-ep8", "deepseek-v2-lite"):
        run = joyai_run()
        run["sut"]["gen"].cfg = get_config(other)
        assert reader(name).read(run) is None, other
    parent = joyai_run()  # the latent family as the parent has it: no count of latent positions
    for edge in (parent["start"], parent["end"], parent["slice"]["start"], parent["slice"]["end"]):
        del edge["perf"]["decode_attn"]
    assert reader(name).read(parent) is None
    dense = joyai_run(kernel="decode_attn_q8_blocked")
    dense["sut"]["gen"].cfg = get_config("qwen3-8b")
    for edge in (dense["start"], dense["end"], dense["slice"]["start"], dense["slice"]["end"]):
        edge["perf"] = {}
    assert reader(name).read(dense) is None
    bare = {"sut": {"gen": joyai_run()["sut"]["gen"]}, "start": {}, "end": {}, "records": [],
            "window": (0.0, 1.0), "device": {"kind": "TPU v5 lite"}}
    assert reader(name).read(bare) is None
    assert reader(name).read({**bare, "sut": {"gen": None}}) is None  # an embedding cell
    idle = joyai_run()
    idle["end"] = idle["start"]  # a window without a decode step
    idle["slice"]["end"] = idle["slice"]["start"]
    assert reader(name).read(idle) is None
    unnamed = joyai_run(kernel="fusion")  # a program whose kernel goes by another name
    if name == "mla_attn_roofline":
        assert reader(name).read(unnamed) is None


ON_CELL = {*NEW, "decode_occupancy", "decode_round_ms", "engine_itl_p95_ms", "window_compiles.serve",
           "pallas_busy_share", "decode_token_yield", "engine_host_ms_per_round",
           "engine_event_gap_p95_ms", "stream_write_lag_p95_ms", "decode_attn_ms",
           "setup_first_dispatch_s.serve", "setup_first_dispatch_s.trace_lower",
           "setup_first_dispatch_s.backend", "event_gap_admit_share", "slot_vacant_ms",
           "slot_vacant_queued_ms", "moe_local_pairs_per_row", "moe_load_max_over_mean"}


def test_the_cell_is_lfm2s_traffic_number_for_number_and_its_entries_are_found_by_name(bench):
    traffic = os.path.join(ROOT, "benchmark", "traffic")
    mine = json.load(open(os.path.join(traffic, CELL + ".json")))
    assert mine == json.load(open(os.path.join(traffic, "lfm2_decode_closed.json")))
    assert (mine["loop"], mine["clients"], mine["temperature"], mine["stagger_first"]) == ("closed", 64, 0.7, True)
    assert mine["prompt_tokens"] == {"dist": "uniform", "lo": 64, "hi": 128} and mine["endpoint"] == "chat"
    assert mine["max_tokens"] == {"dist": "const", "value": 512} and mine["request_timeout_s"] == 120
    assert (mine["preroll_s"], mine["warmup_s"], mine["warmup_rounds_max"]) == (10, 4, 4)
    assert mine["warmup_rounds"] == [{"max_tokens": {"dist": "const", "value": 48}}]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("joyai-llm-flash-ep16-bf16", CELL, 1)
    assert sum(w["config"] == cell["config"] for w in bench["workloads"]) == 1  # ONE cell on it
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["n_routed_experts"] == json.load(open(FILE))["reduced"]
    assert config["file"] == os.path.relpath(FILE, ROOT) and config["source"] == ROW["source_url"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    reports = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"itl_p95_ms", "out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in bench["per_layer"]}
    on_cell = {n for n, m in layer.items() if CELL in m.get("workloads", [CELL])}
    assert on_cell >= ON_CELL, ON_CELL - on_cell  # a later metric may list the cell too
    for m in (*bench["end_to_end"], *bench["per_layer"]):  # appended to a list, never put first
        cells = m.get("workloads", [])
        if CELL in cells and "lfm2_decode_closed" in cells:
            assert cells.index(CELL) > cells.index("lfm2_decode_closed")
    for name in NEW:  # its own entries, each on this cell alone, at the end of the list
        assert layer[name]["workloads"] == [CELL] and layer[name]["moves"] == "out_tokens_per_s"
        mod = reader(name)
        assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            name, layer[name]["unit"], layer[name]["better"], layer[name]["source"],
            layer[name]["layer"], "out_tokens_per_s")
    names = [m["name"] for m in bench["per_layer"]]
    assert [names.index(n) for n in NEW] == sorted(names.index(n) for n in NEW)
    assert min(names.index(n) for n in NEW) > names.index("round_stall_share")  # behind what was there
    # no other cell's kernel or roofline metrics, nor a mixed round's or a state pool's
    for other in ("ssd_decode_ms", "granite_round_roofline", "gdn_decode_ms", "kda_decode_ms",
                  "solar_round_roofline", "kexaone_round_roofline", "lfm2_round_roofline", "win_attn_ms",
                  "decode_round_roofline", "decode_attn_roofline", "mixed_round_ms", "mixed_round_share",
                  "state_pool_share", "moe_grouped_ms", "moe_grouped_roofline",
                  # held to their cells by tests that are there (test_bench_slice, _rounds, _lfm2, _granite)
                  "admit_program_share", "admit_rows_mean", "admit_pad_waste_pct", "event_gap_admit_ms",
                  "round_stall_share", "moe_experts_touched_share", "decode_copy_ms"):
        assert CELL not in layer[other]["workloads"]
