"""What PR 59 adds to the benchmark for the block-diffusion expert-share cell
`sdar_block_closed`: the catalog's `SDAR-30B-A3B-Chat` row through
`check_source.differs`, `load_reference` and `check_sizes` (the configuration's own
file with the module that is there), `reduced` and `published`, the byte functions
against ISSUE 59's arithmetic and the parameter count, the cell's traffic number
for number with JoyAI's, and the three new readers on a hand-made run: each gives
its number from the block rounds' counters, the expert counters and the block
round's name in the trace, and None (so no entry in the result line) on a run
without them, as the parent commit's runs and every other configuration's are.
Entries of BENCHMARK.json are found BY NAME."""

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

from benchmark import check_source, counters, peaks, sdar_bytes  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from llm_mcp_tpu.models.configs import get_config  # noqa: E402

# the round's own time has no reader of its own: in a trace the block round IS the
# plain round (`telemetry/perf.py:PLAIN_ROUND_TRACE_NAME`), which `decode_round_ms` reads
NEW = ["block_tokens_per_pass", "sdar_round_roofline", "block_attn_ms"]
CELL = "sdar_block_closed"
CFG = get_config("sdar-30b-a3b-ep8")
FILE = os.path.join(ROOT, "benchmark", "configs", "sdar-30b-a3b-ep8-bf16.json")
ROW = json.load(open(os.path.join(HERE, "fixtures", "sdar_catalog_row.json")))
EXPERT = 3 * 2048 * 768 * 2  # one expert's three matrices, bfloat16
KV_ROW = 48 * 2 * 4 * (128 + 2)  # a cached position: int8 K and V of 4 heads and a scale each, 48 layers


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


# -- the configuration's own file ------------------------------------------------------


def test_the_configurations_file_is_its_catalog_row_less_what_reduced_lists():
    config = json.load(open(FILE))
    assert check_source.differs(config, ROW) == [] and config["source"] == ROW["source_url"]
    assert config["reduced"] == ["num_experts"] and config["published"] == {"num_experts": 128}
    assert config["num_experts"] == 16 and ROW["config"]["num_experts"] == 128
    for key, value in ROW["config"].items():  # every other key letter for letter: no width, layer or row cut
        if key not in config["reduced"]:
            assert key in config and type(config[key]) is type(value) and config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"], config["num_experts_per_tok"]) == (48, 151936, 8)
    assert not any(check_source.is_width(p) for p in config["reduced"])
    # a cut that `reduced` does not list, or a width, is refused
    assert check_source.differs(dict(config, num_hidden_layers=7), ROW)
    assert check_source.differs(dict(config, reduced=config["reduced"] + ["head_dim"], head_dim=64), ROW)
    assert check_source.differs(dict(config, published={"num_experts": 64}), ROW)
    name, module = bench_run.load_reference(config)
    assert name == "sdar_moe"
    unheld = bench_run.check_sizes(config, CFG, module)
    assert [u.split(" ")[0] for u in unheld] == ["max_position_embeddings", "model_type"]
    module.check(CFG)
    module.check(get_config("tiny-sdar"))
    for other in ("tiny-solar", "tiny-kexaone", "tiny-lfm2", "tiny-llm", "tiny-qwen3", "tiny-moe", "tiny-joyai"):
        with pytest.raises(NotImplementedError):
            module.check(get_config(other))
    assert config["program"]["env"] == {"TPU_MODEL": "sdar-30b-a3b-ep8", "TPU_KV_QUANT": "int8",
                                        "TPU_MAX_SLOTS": 64, "TPU_MAX_SEQ_LEN": 1024}
    # a short prompt and a long reply: generated keys are most of a softmax (what a lost commit shows in)
    assert config["reference_request"] == {"prompt_bytes": 6, "tokens": 64} and config["weights_seed"] == 0
    expect = config["program"]["expect"]
    assert (expect["attn_impl"], expect["kv_quant"]) == ("pallas", "int8")
    assert (expect["_block"], expect["decode_chunk"]) == (4, 4)  # the block the engine reports
    assert (expect["weights_dtype"], expect["expert_dtype"]) == ("", "bfloat16")  # no dense feed-forward
    assert expect["spec_enabled"] is False and expect["ragged_prefill"] is False
    assert expect["_prefix_budget"] == 0 and expect["_pool"] is None and expect["_migrate_in"] is None
    # the generation's sizes are assumed, not model keys the catalog's row lacks
    assert not {"block_length", "block_len", "denoising_steps", "mask_token_id"} & set(config)
    said = " ".join(config["assumed"])
    for word in ("L = 4", "denoising_steps 4", "low_confidence_dynamic", "0.9", "151669", "top-1",
                 "byte tokenizer", "64 slots x 1024", "BLOCK_OFF", "`sequential` rule is absent",
                 "UNSHIFTED", "samples nothing"):
        assert word in said, word
    assert "5,164,972,032" in config["deployment"] and "8-chip" in config["deployment"]
    assert "16 rows an expert a pass where the deployment's 8 x 256 rows give 128" in config["deployment"]


@pytest.mark.parametrize("path,moved", [
    ("num_hidden_layers", 7), ("hidden_size", 1024), ("head_dim", 64), ("num_attention_heads", 16),
    ("num_key_value_heads", 8), ("num_experts", 128), ("num_experts_per_tok", 4),
    ("moe_intermediate_size", 1536), ("intermediate_size", 4096), ("rope_theta", 10_000),
    ("vocab_size", 32_768), ("norm_topk_prob", False), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("max_window_layers", 24), ("use_sliding_window", True),
    ("sliding_window", 4096), ("tie_word_embeddings", True), ("rms_norm_eps", 1e-5),
    ("attention_bias", True), ("hidden_act", "gelu"),
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
        "published.num_experts"}
    assert set(module.HELD) == {"num_experts", "published.num_experts", "decoder_sparse_step",
                                "mlp_only_layers", "max_window_layers", "use_sliding_window"}
    assert module.ONLY == {} and module.STATED == {}
    assert module.HELD["published.num_experts"](CFG) == 128 and module.HELD["num_experts"](CFG) == 16
    assert module.CONTROLS == ("fp8", "int8_head", "no_commit", "causal")
    assert 0.05 < module.SERVED_TOL_REL < 0.6


# -- the byte functions --------------------------------------------------------------


def test_the_byte_functions_are_issue_59s_arithmetic():
    from benchmark import solar_bytes

    assert CFG.param_count() == 5_164_972_032 and round(CFG.param_count() * 2 / 1e9, 2) == 10.33
    layer = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128 + 2 * 2048 + 2048 * 128
    assert layer == 19_140_864 and layer + 16 * 4_718_592 == 94_638_336  # "a layer outside its experts"
    assert 48 * (layer + 128 * 4_718_592) + 622_331_904 == 30_532_122_624  # the uncut model, "61 GB"
    assert 48 * 4 * 128 * 2 == 49_152  # "49,152 bytes a token" of int8 payload
    assert solar_bytes.kv_row_bytes(CFG, "int8") == KV_ROW == 49_152 + 48 * 8 * 2  # and its scales
    assert 48 * 64 * 1024 * (9 * 128 + 8 * 2) == 3_674_210_304  # the fused form with its packed row: "3.67 GB"
    assert round(16 * EXPERT / 1e6) == 151 and round(48 * 16 * EXPERT / 1e9, 2) == 7.25  # the banks a pass
    assert round(48 * 2 * (2 * 2048 * 4096 + 2 * 2048 * 512) / 1e9, 2) == 1.81  # attention weights
    assert round(151_936 * 2048 * 2 / 1e9, 2) == 0.62 and 256 * 151_936 * 4 == 155_582_464  # head; logits
    assert 64 * 4 * 8 * 16 // 128 == 256  # held pairs a layer and pass: 16 rows an expert
    assert round(10.6e9 / 819e9 * 1e3, 1) == 12.9 and round(13.4e9 / 819e9 * 1e3, 1) == 16.4


def sdar_run(program="jit_decode_chunk_fn", passes=4, touched=16.0) -> dict:
    """Counters at both edges (the window: 100 rounds of 60 rows, `passes`
    denoising passes and a commit each; the slice: 40 rounds of 64 rows), the
    expert counts a call a pass, a trace with 10 runs of the block round's
    program of 100 ms (the slice's edges cut the first and the last: eight whole
    runs), and the window's records (64 streams at a mean fill the readers
    integrate)."""
    E, Ln = 16, 48
    params = {"embed": np.zeros((64, 8), np.int16), "lm_head": np.zeros((8, 64), np.int16),
              "final_norm": np.zeros((8,), np.int16),
              "layers": {"router": np.zeros((Ln, 8, 128), np.int16), "wq": np.zeros((Ln, 8, 16), np.int16),
                         "w1e": np.zeros((Ln, E, 4, 6), np.int16), "w3e": np.zeros((Ln, E, 4, 6), np.int16),
                         "w2e": np.zeros((Ln, E, 6, 4), np.int16)}}
    gen = SimpleNamespace(cfg=CFG, params=params, kv_quant="int8", decode_chunk=4, max_slots=64,
                          max_seq_len=1024)

    def book(rounds, rows):
        calls = rounds * (passes + 1)
        counts = [[calls * rows * 4, calls * rows * 4, calls * touched, calls * 20, calls] for _ in range(Ln)]
        return {"experts": {"counts": [counts, [[0] * 5] * Ln], "held": E, "router": 128},
                "blocks": {"rounds": rounds, "rows": rounds * rows, "passes": rounds * passes,
                           "commits": rounds, "unmasked": rounds * rows * 4 - rounds,
                           "remainder_tokens": rounds, "delivered": rounds * rows * 4 - 3 * rounds,
                           "by_passes": {str(passes): rounds * rows}, "off": {}}}

    def plus(a, b):
        return {"experts": {"counts": [[[x + y for x, y in zip(r, s)] for r, s in zip(
                    a["experts"]["counts"][0], b["experts"]["counts"][0])], [[0] * 5] * Ln],
                            "held": E, "router": 128},
                "blocks": {k: (a["blocks"][k] + b["blocks"][k] if k in sdar_bytes.SUMS else a["blocks"][k])
                           for k in a["blocks"]}}

    mods = [(f"{program}(77)", r * 110e6, r * 110e6 + 100e6) for r in range(10)]
    first, more = book(30, 60), book(40, 64)
    records = [{"status": 200, "finish": "length", "prompt_tokens": 100, "completion_tokens": 512,
                "t_first": 0.0, "t_last": 60.0, "t_send": 0.0, "chunks": [0.0, 60.0]} for _ in range(64)]
    return {"sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"},
            "start": {"perf": book(0, 60)}, "end": {"perf": book(100, 60)},
            "records": records, "window": (10.0, 50.0),
            "slice": {"start": {"perf": first}, "end": {"perf": plus(first, more)},
                      "window": (26.0, 34.0), "window_abs": (126.0, 134.0), "rounds": []},
            "trace_reduced": {"module_runs": {program: [10, 0.100]}, "whole_runs": {program: [8, 0.100]}},
            "_planes": ([(0, [], mods)], {})}


def test_each_new_reader_gives_its_number_on_a_run_with_the_counters_and_the_program(capsys, monkeypatch):
    run = sdar_run()
    monkeypatch.setattr(counters, "mean_live_tokens", lambda r: 64 * 300.0)  # 64 rows at 300 positions
    monkeypatch.setattr(sdar_bytes, "attn_round_s", lambda r: 0.020)  # the scoped operations: a raw trace's
    got = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    cut = counters.slice_of(run)
    gen = run["sut"]["gen"]
    one = sdar_bytes.one_expert_bytes(gen)
    assert one == 3 * 4 * 6 * 2 and sdar_bytes.bank_bytes(gen) == 48 * 16 * one
    rest = peaks.decode_weight_bytes(gen.params) - sdar_bytes.bank_bytes(gen)
    assert rest == 2 * (8 * 64 + 8 + 48 * (8 * 128 + 8 * 16))  # the table left out, the head read
    assert sdar_bytes.head_bytes(gen) == 2 * 8 * 64
    assert sdar_bytes.blocks(cut)["rounds"] == 40 and sdar_bytes.blocks(run)["rounds"] == 100
    assert sdar_bytes.passes_a_round(cut) == (4.0, 1.0)
    a_pass = rest + 48 * 16 * one + KV_ROW * 64 * 300.0
    assert sdar_bytes.pass_bytes(cut) == pytest.approx(a_pass)
    need = 4 * a_pass + (a_pass - 2 * 8 * 64 + 64 * 4 * KV_ROW)
    assert sdar_bytes.round_bytes(cut) == pytest.approx(need)
    assert reader("decode_round_ms").read(run) == pytest.approx(100.0)  # the accepted reader, this round
    assert got["sdar_round_roofline"] == pytest.approx(100 * need / 819e9 / 0.100)
    assert 0 < got["sdar_round_roofline"] < 100
    # positions filled a row over the passes a round ran: (240 - 1) / 60 over 5
    assert got["block_tokens_per_pass"] == pytest.approx((60 * 4 - 1) / 60 / 5)
    assert got["block_attn_ms"] == pytest.approx(20.0)
    out = capsys.readouterr().out
    assert "a round of 4.00 denoising passes and 1.00 commits" in out  # the roofline reader logs them
    greedy = sdar_run(passes=1)  # one pass fills a block: two passes for four tokens
    assert reader("block_tokens_per_pass").read(greedy) == pytest.approx((60 * 4 - 1) / 60 / 2)
    fewer = sdar_run(touched=8.0)
    assert sdar_bytes.pass_bytes(counters.slice_of(fewer)) == pytest.approx(a_pass - 48 * 8 * one)


def test_the_weights_a_pass_reads_are_the_parameter_count_less_the_table():
    """With every held expert touched, the weights' part of `pass_bytes` is every
    parameter but the embedding table once: the byte functions count nothing
    twice and leave nothing out (the tiny preset's own tree, float32)."""
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.models.llama import init_llama_params

    cfg = get_config("tiny-sdar")
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    gen = SimpleNamespace(cfg=cfg, params=params)
    assert sdar_bytes.is_ours(gen) and sdar_bytes.one_expert_bytes(gen) == 3 * 64 * 32 * 4
    whole = (peaks.decode_weight_bytes(params) - sdar_bytes.bank_bytes(gen)
             + cfg.n_layers * cfg.n_experts * sdar_bytes.one_expert_bytes(gen))
    assert whole == 4 * (cfg.param_count() - cfg.vocab_size * cfg.dim)
    assert sdar_bytes.head_bytes(gen) == 4 * cfg.vocab_size * cfg.dim


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_gives_nothing_where_the_program_lacks_what_it_reads(name):
    """The parent commit given this cell's files (its engine has no block rounds:
    no counters, no such program in a trace), the other cells (a decode round's
    program and no `blocks`), a bare run, an embedding cell, a window without a
    round."""
    parent = sdar_run()
    for edge in (parent["start"], parent["end"], parent["slice"]["start"], parent["slice"]["end"]):
        del edge["perf"]["blocks"]
    parent["sut"]["gen"].cfg = get_config("joyai-llm-flash-ep16")
    assert reader(name).read(parent) is None
    for other in ("k-exaone-236b-ep8", "lfm2-8b-a1b-d14", "solar-open2-250b-ep8", "qwen3-8b"):
        run = sdar_run()
        run["sut"]["gen"].cfg = get_config(other)
        for edge in (run["start"], run["end"], run["slice"]["start"], run["slice"]["end"]):
            del edge["perf"]["blocks"]
        assert reader(name).read(run) is None, other
    bare = {"sut": {"gen": sdar_run()["sut"]["gen"]}, "start": {}, "end": {}, "records": [],
            "window": (0.0, 1.0), "device": {"kind": "TPU v5 lite"}}
    assert reader(name).read(bare) is None
    assert reader(name).read({**bare, "sut": {"gen": None}}) is None  # an embedding cell
    idle = sdar_run()
    idle["end"], idle["slice"]["end"] = idle["start"], idle["slice"]["start"]  # no round
    idle["trace_reduced"] = {"module_runs": {}, "whole_runs": {}}
    idle["_planes"] = ([(0, [], [])], {})
    assert reader(name).read(idle) is None


ON_CELL = {*NEW, "decode_round_ms"}


def test_the_cell_is_joyais_traffic_number_for_number_and_its_entries_are_found_by_name(bench):
    traffic = os.path.join(ROOT, "benchmark", "traffic")
    mine = json.load(open(os.path.join(traffic, CELL + ".json")))
    assert mine == json.load(open(os.path.join(traffic, "joyai_decode_closed.json")))
    assert (mine["loop"], mine["clients"], mine["temperature"], mine["stagger_first"]) == ("closed", 64, 0.7, True)
    assert mine["prompt_tokens"] == {"dist": "uniform", "lo": 64, "hi": 128} and mine["endpoint"] == "chat"
    assert mine["max_tokens"] == {"dist": "const", "value": 512} and mine["request_timeout_s"] == 120
    assert (mine["preroll_s"], mine["warmup_s"], mine["warmup_rounds_max"]) == (10, 4, 4)
    assert mine["warmup_rounds"] == [{"max_tokens": {"dist": "const", "value": 48}}]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar-30b-a3b-ep8-bf16", CELL, 1)
    assert sum(w["config"] == cell["config"] for w in bench["workloads"]) == 1  # ONE cell on it
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["num_experts"] == json.load(open(FILE))["reduced"]
    assert config["file"] == os.path.relpath(FILE, ROOT) and config["source"] == ROW["source_url"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    reports = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"itl_p95_ms", "out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in bench["per_layer"]}
    on_cell = {n for n, m in layer.items() if CELL in m.get("workloads", [CELL])}
    assert on_cell >= ON_CELL, ON_CELL - on_cell  # a later metric may list the cell too
    for m in (*bench["end_to_end"], *bench["per_layer"]):  # appended to a list, never put first
        cells = m.get("workloads", [])
        if CELL in cells and "joyai_decode_closed" in cells:
            assert cells.index(CELL) > cells.index("joyai_decode_closed")
    for name in NEW:  # its own entries, each on this cell alone, at the end of the list
        assert layer[name]["workloads"] == [CELL] and layer[name]["layer"] == "step programs"
        assert layer[name]["moves"] == "out_tokens_per_s"
        mod = reader(name)
        assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            name, layer[name]["unit"], layer[name]["better"], layer[name]["source"],
            layer[name]["layer"], layer[name]["moves"])
    names = [m["name"] for m in bench["per_layer"]]
    assert [names.index(n) for n in NEW] == sorted(names.index(n) for n in NEW)
    assert min(names.index(n) for n in NEW) > names.index("latent_cache_bytes_share")  # behind what was there
    # another program's readers read nothing here, and the lists other tests pin stay as they are
    assert sdar_bytes.PROGRAM == counters.DECODE_PROGRAM  # the round's program under the decode round's name
    for other in ("decode_attn_ms", "decode_round_roofline", "decode_attn_roofline",
                  "joyai_round_roofline", "mla_attn_roofline", "mixed_round_ms", "mixed_round_share",
                  "state_pool_share", "moe_grouped_ms", "moe_grouped_roofline",
                  "admit_program_share", "admit_rows_mean", "admit_pad_waste_pct", "event_gap_admit_ms",
                  "round_stall_share", "moe_experts_touched_share", "decode_copy_ms"):
        assert CELL not in layer[other]["workloads"]
