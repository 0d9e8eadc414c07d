"""What PR 43 adds to the benchmark for the window-and-global cell
`kexaone_reason_closed`: the configuration's file held to its row of the catalog
(six cuts of scale, no width) and to the program's table, the reference
module's tables, the byte functions against ISSUE 43's arithmetic, and the five
readers on a hand-made run and on the recorded trace: each gives its number from
the window arm's name in the trace and the program's counters, and None (so no
entry in the result line) on a run without them, as the parent commit's runs and
every other configuration's are. Entries of BENCHMARK.json are found BY NAME."""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import counters, kexaone_bytes, solar_bytes  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import trace_reduce  # noqa: E402
from llm_mcp_tpu.models.configs import get_config  # noqa: E402

NEW = ["kexaone_round_roofline", "win_attn_ms", "win_attn_roofline", "kv_window_bytes_share",
       "full_attn_roofline"]
CELL = "kexaone_reason_closed"
NAME = "k-exaone-236b-ep8-bf16"
CFG = get_config("k-exaone-236b-ep8")
FILE = os.path.join(ROOT, "benchmark", "configs", NAME + ".json")
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "sliding_windows",
           "num_experts", "vocab_size"]
POSITION = 2 * 8 * (128 + 2)  # int8 K and V of one position of one layer, a scale a head
FULL = 64 * (17 * 4096 * 128 + 16 * 4096 * 2)  # the global layer's cache, payload and scales
RING = 4 * 64 * (17 * 128 * 128 + 16 * 128 * 2)  # four rings of 128 positions


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def edge(rounds: int, touched: int = 11) -> dict:
    """Counters at one edge: `rounds` x 4 decode steps of 60 rows since boot in
    each of the four expert layers, 11 held experts touched a step."""
    steps = 4 * rounds
    counts = [[60 * steps, 220 * steps, touched * steps, 30 * steps, steps] for _ in range(4)]
    return {"perf": {
        "experts": {"counts": [counts, [[0] * 5] * 4], "held": 16, "router": 128},
        "kv_kinds": {"full": {"layers": 1, "bytes": FULL, "positions": 64 * 4096, "live_positions": 0},
                     "window": {"layers": 4, "bytes": RING, "positions": 64 * 128,
                                "live_positions": 0}}}}


def kexaone_run(kernel: str = "decode_attn_win_q8") -> dict:
    """Counters at both edges, 60 requests in flight through the whole window
    (prompts of 800 tokens growing by 1000), a trace with 10 runs of the decode
    program of 50 ms, each holding 16 calls of the window arm of 0.05 ms and 4 of
    the blocked arm of 0.4 ms (the slice's edges cut the first and the last:
    eight whole runs), and the traced slice as `run.measure` records it: late in
    the window, where the sequences are 140 tokens longer than at its middle,
    and its steps touch 7 held experts where the window's touch 11."""
    params = {"embed": np.zeros((64, 8), np.int8), "final_norm": np.zeros((8,), np.int8),
              "lm_head": np.zeros((8, 64), np.int8),
              "layers": {"attn_norm": np.zeros((4, 8), np.int8),
                         **{k: np.zeros((4, 16, 8, 4), np.int8) for k in ("w1e", "w3e", "w2e")}},
              "first": [{"attn_norm": np.zeros((8,), np.int8), "w1": np.zeros((8, 32), np.int8),
                         "wq": np.zeros((8, 8), np.int8)}],
              "gqa": {"wq": np.zeros((1, 8, 8), np.int8)}, "win": {"wq": np.zeros((3, 8, 8), np.int8)}}
    gen = SimpleNamespace(cfg=CFG, params=params, kv_quant="int8", decode_chunk=4, max_slots=64)
    ops, mods = [], []
    for r in range(10):
        t0 = r * 60e6
        mods.append(("jit_decode_chunk_fn(77)", t0, t0 + 50e6))
        for c in range(16):
            a = t0 + 1e6 + c * 0.1e6
            ops.append((f"%{kernel}.{c} = bf16[64,8,8,128] custom-call(...)", a, a + 0.05e6))
        for c in range(4):
            a = t0 + 10e6 + c * 1e6
            ops.append((f"%decode_attn_q8_blocked.{c} = bf16[64,8,8,128] custom-call(...)", a, a + 0.4e6))
    ops.append((f"%{kernel}.999 = bf16[1] custom-call(...)", 700e6, 701e6))  # outside any run
    records = [{"status": 200, "error": None, "finish": "length", "sent": 0.0, "done": 100.0,
                "events": [0.0, 100.0], "prompt_tokens": 800, "completion_tokens": 1000}
               for _ in range(60)]
    return {"sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"},
            "start": edge(0), "end": edge(100), "records": records, "window": (10.0, 50.0),
            "slice": {"start": edge(0, touched=7), "end": edge(3, touched=7), "window": (40.0, 48.0),
                      "window_abs": (140.0, 148.0), "rounds": [("decode", 60, 141.0)]},
            "trace_reduced": {"module_runs": {"jit_decode_chunk_fn": [10, 0.050]},
                              "whole_runs": {"jit_decode_chunk_fn": [8, 0.050]}},
            "_planes": ([(0, ops, mods)], {})}


def test_the_byte_functions_are_issue_43s_arithmetic():
    assert kexaone_bytes.position_bytes(CFG, "int8") == POSITION == 2080  # "2.1 KB"
    assert kexaone_bytes.position_bytes(CFG, "") == 2 * 8 * 128 * 2
    assert kexaone_bytes.window_layers(CFG) == 4 and CFG.n_attn_layers == 1
    assert round(FULL / 1e9, 2) == 0.58 and round(RING / 1e9, 3) == 0.072  # "0.55 GB" at 2.1 KB a token
    assert round(CFG.param_count() / 1e6) == 3712 and round(CFG.param_count() * 2 / 1e9, 2) == 7.42
    one_expert = 3 * 6144 * 2048 * 2
    assert round(one_expert / 1e6, 1) == 75.5
    # the 64 held banks of the four expert layers, all touched at 64 pairs a layer: 4.8 GB of a step
    assert round(4 * 16 * one_expert / 1e9, 2) == 4.83
    banks, rest = 4 * 16 * one_expert, CFG.param_count() * 2 - 4 * 16 * one_expert - 19_200 * 6144 * 2
    assert round((rest + banks) / 1e9, 1) == 7.2  # the weights a step reads where every bank is touched
    # four fifths of the cache's bytes if the window layers kept full-length rows
    assert round(4 * FULL / (5 * FULL), 1) == 0.8 and round(RING / (RING + FULL), 3) == 0.111


def test_live_positions_cap_a_window_layers_share_of_a_sequence():
    run = kexaone_run()
    fill = kexaone_bytes.mean_live_positions(run)
    assert fill == pytest.approx(60 * (800 + 1000 * 0.3), rel=0.01)  # the window's middle: 30 of 100 s
    assert fill == pytest.approx(counters.mean_live_tokens(run))
    assert kexaone_bytes.mean_live_positions(run, cap=128) == 60 * 128
    short = kexaone_run()
    for r in short["records"]:
        r.update(prompt_tokens=20, completion_tokens=0)
    assert kexaone_bytes.mean_live_positions(short, cap=128) == 60 * 20
    assert kexaone_bytes.win_step_bytes(run) == 4 * POSITION * 60 * 128


def test_each_new_reader_gives_its_number_on_a_run_with_the_arm_and_the_counters():
    run = kexaone_run()
    got = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    assert got["win_attn_ms"] == pytest.approx(16 * 0.05)  # the stray call outside a run is not read
    assert reader("decode_attn_ms").read(run) == pytest.approx(16 * 0.05 + 4 * 0.4)  # both arms
    need = 4 * 4 * POSITION * 60 * 128
    assert got["win_attn_roofline"] == pytest.approx(100 * need / 819e9 / 0.8e-3)
    assert 0 < got["win_attn_roofline"] < 100
    # the global layer's arm: the decode attention kernels' time less the window arm's
    # the fill of the SLICE's middle (44 of 100 s: 1240 tokens a sequence), whose time this is;
    # the window's middle reads 1100
    assert got["full_attn_roofline"] == pytest.approx(
        100 * 4 * POSITION * 60 * 1240 / 819e9 / (4 * 0.4e-3), rel=0.01)
    assert 0 < got["full_attn_roofline"] < 100
    assert got["kv_window_bytes_share"] == pytest.approx(100 * RING / (RING + FULL))
    weights = 8 + 8 * 64 + 5 * 8 + 1 * 8 * 32 + 1 * 8 * 8 + 4 * 8 * 8  # all but the table and the banks
    one_expert = 3 * 8 * 4
    step = kexaone_bytes.decode_step_bytes(counters.slice_of(run))  # and the slice's 7 touched experts
    assert step == pytest.approx(weights + 4 * 7 * one_expert + POSITION * 60 * 1240
                                 + 4 * POSITION * 60 * 128, rel=0.01)
    assert kexaone_bytes.decode_step_bytes(run) == pytest.approx(  # the window's, no reader's
        weights + 4 * 11 * one_expert + POSITION * 60 * 1100 + 4 * POSITION * 60 * 128, rel=0.01)
    assert got["kexaone_round_roofline"] == pytest.approx(100 * 4 * step / 819e9 / 0.050)
    assert 0 < got["kexaone_round_roofline"] < 100
    assert solar_bytes.live_rows(run) == pytest.approx(60.0)
    assert reader("moe_local_pairs_per_row").read(run) == pytest.approx((220 / 60) / (8 * 16 / 128))
    assert reader("moe_load_max_over_mean").read(run) == pytest.approx(30 * 16 / 220)


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_gives_nothing_where_the_program_lacks_what_it_reads(name):
    """The parent commit given this cell's files (no `kv_kinds`, no window arm),
    Solar's cell (experts, no rings), decode_closed, a bare run, an untraced
    run, and the recorded v5e trace of decode_closed."""
    parent = kexaone_run(kernel="decode_attn_q8_whole")
    for e in (parent["start"], parent["end"], parent["slice"]["start"], parent["slice"]["end"]):
        del e["perf"]["kv_kinds"]
    assert reader(name).read(parent) is None
    solar = kexaone_run(kernel="kda_decode_step")
    solar["sut"]["gen"].cfg = get_config("solar-open2-250b-ep8")
    for e in (solar["start"], solar["end"], solar["slice"]["start"], solar["slice"]["end"]):
        del e["perf"]["kv_kinds"]["window"]  # every layer with rows keeps full-length ones
    assert reader(name).read(solar) is None
    bare = {"sut": {"gen": kexaone_run()["sut"]["gen"]}, "start": {}, "end": {}, "records": [],
            "window": (0.0, 1.0), "device": {"kind": "TPU v5 lite"}}
    assert reader(name).read(bare) is None
    if name != "kv_window_bytes_share":  # a counter: an untraced run reads it too
        untraced = kexaone_run()
        untraced["_planes"], untraced["trace_reduced"] = None, None
        assert reader(name).read(untraced) is None
        recorded = kexaone_run()
        path = os.path.join(ROOT, "benchmark", "fixtures", "v5e_decode_slice.xspace.txt")
        recorded["_planes"] = trace_reduce.read_planes(path)
        recorded["trace_reduced"] = trace_reduce.reduce_trace(path)
        # the arm is not there, and its one run of the decode program is cut by the slice's
        # edge: no whole run, so no round's time either
        assert reader(name).read(recorded) is None


def test_the_other_cells_kernel_readers_find_nothing_on_this_cell():
    run = kexaone_run()
    for name in ("kda_decode_ms", "kda_decode_roofline", "gdn_decode_ms", "gdn_decode_roofline",
                 "ssd_decode_ms", "ssd_decode_roofline", "granite_round_roofline", "olmo_round_roofline"):
        assert reader(name).read(run) is None


def test_the_configurations_file_is_its_catalog_row_less_six_cuts_of_scale():
    from benchmark import check_source

    config = json.load(open(FILE))
    rows = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "kexaone_catalog_row.jsonl")
    row = next(r for r in map(json.loads, open(rows)) if r["name"] == "K-EXAONE-236B-A23B")
    assert check_source.differs(config, row) == []
    assert config["source"] == row["source_url"] and config["reduced"] == REDUCED
    assert not any(check_source.is_width(p) for p in REDUCED)
    assert set(config["published"]) == set(REDUCED)
    for key, value in row["config"].items():  # every key under its own name; a cut states the source's
        assert key in config, key
        assert (config["published"][key] if key in REDUCED else config[key]) == value, key
    assert config["num_hidden_layers"] == 5 and config["num_experts"] == 16
    assert config["layer_types"] == row["config"]["layer_types"][:5]
    assert config["sliding_windows"] == [128, 128, 128, 0, 128]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4 and config["vocab_size"] == 19_200
    name, module = bench_run.load_reference(config)
    assert name == "exaone_moe"
    unheld = bench_run.check_sizes(config, CFG, module)
    assert [u.split(" ")[0] for u in unheld] == ["max_position_embeddings", "model_type"]
    module.check(CFG)
    module.check(get_config("tiny-kexaone"))
    for other in ("tiny-solar", "tiny-olmo-hybrid", "tiny-granite-hybrid", "tiny-llm", "tiny-mistral"):
        with pytest.raises(NotImplementedError):
            module.check(get_config(other))
    said = " ".join(config["assumed"])
    for what in ("OUTPUT", "RMS-normed a head", "does NOT rotate", "selection bias", "DeepSeek-V3's",
                 "seeded random weights", "byte tokenizer", "64 slots x 4096"):
        assert what in said, what
    assert config["program"]["env"] == {
        "TPU_MODEL": "k-exaone-236b-ep8", "TPU_KV_QUANT": "int8", "TPU_MAX_SLOTS": 64,
        "TPU_MAX_SEQ_LEN": 4096, "TPU_PREFILL_CHUNK": 1024, "TPU_WARMUP_BG": 0}
    assert config["reference_request"] == {"prompt_bytes": 700, "tokens": 16}  # five windows and more
    expect = config["program"]["expect"]
    assert (expect["expert_dtype"], expect["weights_dtype"], expect["kv_quant"]) == (
        "bfloat16", "bfloat16", "int8")
    assert (expect["attn_impl"], expect["decode_impl"]) == ("pallas", "pallas")


@pytest.mark.parametrize("path,moved", [
    ("layer_types", ["full_attention"] * 5), ("sliding_windows", [128] * 5),
    ("sliding_windows", [128, 128, 0, 128, 128]), ("sliding_window_pattern", "LG"),
    ("mlp_layer_types", ["sparse"] * 5), ("first_k_dense_replace", 0), ("num_experts", 128),
    ("num_shared_experts", 2), ("scoring_func", "softmax"), ("routed_scaling_factor", 1.0),
    ("norm_topk_prob", False), ("n_group", 8), ("topk_group", 4), ("num_experts_per_tok", 6),
    ("moe_intermediate_size", 1024), ("intermediate_size", 2048), ("sliding_window", 256),
    ("num_nextn_predict_layers", 0), ("mtp_layer_types", ["sliding_attention"]),
    ("mtp_sliding_windows", [128]), ("head_dim", 64), ("num_key_value_heads", 4),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"), ("vocab_size", 153_600),
])
def test_a_key_of_the_file_that_is_not_the_programs_stops_the_run(path, moved):
    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    config[path] = moved
    with pytest.raises(AssertionError, match=path):
        bench_run.check_sizes(config, CFG, module)


@pytest.mark.parametrize("group,key,moved", [
    ("rope_parameters", "rope_theta", 10_000), ("rope_parameters", "rope_type", "yarn"),
    ("published", "num_experts", 64),
])
def test_a_key_inside_a_group_is_held_too(group, key, moved):
    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    config[group] = dict(config[group], **{key: moved})
    with pytest.raises(AssertionError, match=key):
        bench_run.check_sizes(config, CFG, module)


def test_the_tables_name_every_key_run_py_does_not_hold_and_the_controls_move_the_logits():
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.models.llama import init_llama_params

    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    own = bench_run.own_paths()
    model = {p for p in bench_run.model_paths(config)}
    tables = set(module.HELD) | set(module.ONLY) | set(module.STATED)
    assert model - own == {p for p in tables if not p.startswith("published.")}
    assert not tables & own  # a module may not hold again what run.py holds
    assert {"num_experts", "published.num_experts", "num_shared_experts", "scoring_func", "layer_types",
            "mlp_layer_types", "sliding_windows", "sliding_window_pattern", "num_nextn_predict_layers",
            "rope_parameters.rope_theta"} <= set(module.HELD)
    assert {"mtp_layer_types", "mtp_sliding_windows", "rope_parameters.rope_type"} <= set(module.ONLY)
    assert module.HELD["sliding_window_pattern"](CFG) == "LLLG"
    assert module.CONTROLS == ("fp8", "no_window", "rope_global", "no_scale", "lost_ring")
    assert 0.05 < module.SERVED_TOL_REL < 0.5 and module.RING == CFG.ring_len
    cfg = get_config("tiny-kexaone")
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (192,), 3, 500))
    rows, cols = np.arange(176, 192), np.arange(cfg.vocab_size)
    plain = module.logits(cfg, params, toks, rows, cols)
    moved = {}
    try:
        for lower in module.CONTROLS:
            module.LOWER = lower
            jax.clear_caches()
            moved[lower] = float(np.max(np.abs(module.logits(cfg, params, toks, rows, cols) - plain)))
    finally:
        module.LOWER = None
        jax.clear_caches()
    assert all(v > 0.1 for v in moved.values()), moved


ON_CELL = {*NEW, "decode_occupancy", "decode_round_ms", "engine_itl_p95_ms", "window_compiles.serve",
           "pallas_busy_share", "decode_token_yield", "engine_host_ms_per_round",
           "engine_event_gap_p95_ms", "stream_write_lag_p95_ms", "decode_attn_ms",
           "setup_first_dispatch_s.serve", "setup_first_dispatch_s.trace_lower",
           "setup_first_dispatch_s.backend", "moe_local_pairs_per_row", "moe_load_max_over_mean",
           "state_pool_share", "admit_program_share", "admit_rows_mean", "admit_pad_waste_pct",
           "event_gap_admit_share", "event_gap_admit_ms", "slot_vacant_ms",
           "slot_vacant_queued_ms"}  # what PR 43 put on the cell; a later metric may list it too


def test_the_cell_is_reasoning_traffic_and_its_entries_are_found_by_name(bench):
    mine = json.load(open(os.path.join(ROOT, "benchmark", "traffic", CELL + ".json")))
    assert (mine["loop"], mine["clients"], mine["endpoint"]) == ("closed", 64, "chat")
    # bytes of prompt text; the chat template's "user: " and the first token make them 640-1024 tokens
    assert mine["prompt_tokens"] == {"dist": "uniform", "lo": 633, "hi": 1017}
    assert mine["max_tokens"] == {"dist": "const", "value": 2048}
    # ISSUE 43's traffic, the other generation cells' temperature: with seeded weights a reply
    # sampled at 0.7 ends at EOS after some 300 tokens whatever max_tokens says (PERF.md section 4)
    assert (mine["temperature"], mine["stagger_first"], mine["preroll_s"]) == (0.7, True, 10)
    assert mine["warmup_rounds"] == [{"max_tokens": {"dist": "const", "value": 48}}]
    solar = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "solar_decode_closed.json")))
    apart = ("prompt_tokens", "max_tokens")
    assert {k: v for k, v in mine.items() if k not in apart} == {
        k: v for k, v in solar.items() if k not in apart}
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, CELL, 1)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == REDUCED and config["file"] == os.path.relpath(FILE, ROOT)
    assert config["source"] == json.load(open(FILE))["source"]
    reports = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"itl_p95_ms", "out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in bench["per_layer"]}
    on_cell = {n for n, m in layer.items() if CELL in m.get("workloads", [CELL])}
    assert on_cell >= ON_CELL, ON_CELL - on_cell
    for name in NEW:  # its own entries, each on this cell alone
        assert layer[name]["workloads"] == [CELL] and layer[name]["moves"] == "out_tokens_per_s"
        mod = reader(name)
        assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER, mod.BETTER) == (
            name, layer[name]["unit"], layer[name]["source"], layer[name]["layer"],
            layer[name]["better"])
    # no other cell's kernel metrics were put on this one
    for other in ("gdn_decode_ms", "kda_decode_ms", "ssd_decode_ms", "solar_round_roofline",
                  "olmo_round_roofline", "granite_round_roofline", "decode_round_roofline",
                  "decode_attn_roofline", "decode_copy_ms"):
        assert CELL not in layer[other]["workloads"]
