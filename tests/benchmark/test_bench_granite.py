"""What PR 41 adds to the benchmark for the state-space hybrid cell
`granite_decode_closed`: the configuration's file held to its row of the
catalog (whole: nothing reduced) and to the program's table, the reference
module's tables, the byte functions against ISSUE 41's arithmetic, and the three
readers on a hand-made run and on the recorded trace: each gives its number from
kernel names in the trace and the perf observatory's phases, and None (so no
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

from benchmark import counters, granite_bytes, olmo_hybrid_bytes, solar_bytes  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import trace_reduce  # noqa: E402
from llm_mcp_tpu.models.configs import get_config  # noqa: E402

NEW = ["ssd_decode_ms", "ssd_decode_roofline", "granite_round_roofline"]
CELL = "granite_decode_closed"
CFG = get_config("granite-4.0-h-micro")
FILE = os.path.join(ROOT, "benchmark", "configs", "granite-4.0-h-micro-bf16.json")
STATE = 64 * 128 * 64 * 4  # a slot's float32 state of one state-space layer
TAILS = 3 * 4352 * 2  # and its convolution tails, bfloat16


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def phases(rounds: int, rows: int) -> dict:
    return {"phases": {"decode": {"samples": rounds, "tokens": rounds * rows * 4}}}


def granite_run(kernel: str = "ssd_decode_step") -> dict:
    """Counters at both edges (100 sampled rounds of 60 rows), a trace with 10
    runs of the decode program of 100 ms, each holding 144 calls of the state
    kernel of 0.4 ms (the slice's edges cut the first and the last: eight whole
    runs), no request in flight (no KV to count), and the traced slice as
    `run.measure` records it: no sample of the observatory inside it, and of
    the rounds dispatched in it the plain ones carry 30 rows, the window's 60."""
    params = {"embed": np.zeros((64, 8), np.int8), "final_norm": np.zeros((8,), np.int8),
              "layers": {"w1": np.zeros((40, 8, 16), np.int8)},
              "gqa": {"wq": np.zeros((4, 8, 8), np.int8)},
              "ssm": {"w_in": np.zeros((36, 8, 24), np.int8)}}
    gen = SimpleNamespace(cfg=CFG, params=params, kv_quant="int8", decode_chunk=4, max_slots=64)
    ops, mods = [], []
    for r in range(10):
        t0 = r * 120e6
        mods.append(("jit_decode_chunk_fn(77)", t0, t0 + 100e6))
        for c in range(144):
            a = t0 + 1e6 + c * 0.6e6
            ops.append((f"%{kernel}.{c} = (f32[64,2,16,128], f32[36,64,32,128,128]) custom-call(...)",
                        a, a + 0.4e6))
    ops.append((f"%{kernel}.999 = (f32[1]) custom-call(...)", 1300e6, 1301e6))  # outside any run
    return {"sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"},
            "start": {"perf": phases(0, 60)}, "end": {"perf": phases(100, 60)},
            "records": [], "window": (10.0, 50.0),
            "slice": {"start": {"perf": phases(50, 60)}, "end": {"perf": phases(50, 60)},
                      "window": (26.0, 34.0), "window_abs": (126.0, 134.0),
                      "rounds": [("decode", 30, 126.5), ("mixed", 64, 128.0), ("decode", 30, 130.0)]},
            "trace_reduced": {"module_runs": {"jit_decode_chunk_fn": [10, 0.100]},
                              "whole_runs": {"jit_decode_chunk_fn": [8, 0.100]}},
            "_planes": ([(0, ops, mods)], {})}


def test_the_byte_functions_are_issue_41s_arithmetic():
    assert granite_bytes.ssm_layers(CFG) == 36
    row = granite_bytes.kernel_row_bytes(CFG)
    # the state twice; B and C once a row (one group); dt x in, y out; a decay a head
    assert row == 2 * STATE + 4 * (2 * 128 + 2 * 64 * 64 + 64)
    assert granite_bytes.kernel_step_bytes(CFG, 64) == 36 * 64 * row
    assert granite_bytes.state_step_bytes(CFG, 64) == 36 * 64 * (row + 2 * TAILS)
    assert round(36 * STATE / 1e6, 1) == 75.5  # "75.5 MB a slot"
    assert round(36 * 64 * 2 * STATE / 1e9, 1) == 9.7  # "64 x 75.5 MB x 2 = 9.7 GB a step"
    assert round(36 * TAILS / 1e6, 2) == 0.94
    assert solar_bytes.kv_row_bytes(CFG, "int8") == 4 * 8 * 2 * (64 + 2) == 4224  # "4.2 KB a token"
    assert round((36 * 64 * (STATE + TAILS)) / 1e9, 2) == 4.89  # the pool: 4.83 GB of state + 0.06 of tails
    assert round(CFG.param_count() * 2 / 1e9, 2) == 6.38
    # the state is three fifths of a step's least bytes
    share = 36 * 64 * 2 * STATE / (36 * 64 * 2 * STATE + CFG.param_count() * 2 + 64 * 1024 * 4224 / 2)
    assert 0.59 < share < 0.61


def test_the_pool_the_program_allocates_holds_its_logical_bytes():
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.executor.memory import build_state_pool
    from llm_mcp_tpu.models.ssm import init_ssm_state

    state = jax.eval_shape(lambda: init_ssm_state(CFG, 36, 64, jnp.bfloat16))
    assert state["S"].shape == (36, 64, 32, 128, 128) and state["conv"].shape == (36, 64, 3 * 4352)
    pool = build_state_pool(CFG, 64, state, SimpleNamespace(info=lambda *a: None))
    stats = pool.stats(live_slots=32)
    assert stats["bytes"] == 36 * 64 * (STATE + TAILS)
    assert stats["bytes_per_slot"] == 36 * (STATE + TAILS) and stats["live_bytes"] * 2 == stats["bytes"]
    assert stats["layout"] == {"S": [36, 64, 32, 128, 128], "conv": [36, 64, 13_056]}
    assert all(d % 128 == 0 for d in (stats["layout"]["S"][-1], stats["layout"]["conv"][-1]))
    assert set(stats["off"]) >= {"prefix_cache", "offload", "migration", "speculation",
                                 "ragged_prefill", "mixed_round"}


def test_each_new_reader_gives_its_number_on_a_run_with_the_kernel_and_the_phases():
    run = granite_run()
    got = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    cut = counters.slice_of(run)
    assert granite_bytes.live_rows(run) == pytest.approx(60.0)  # the window's sampled rounds
    assert granite_bytes.live_rows(cut) == pytest.approx(30.0)  # the slice's plain rounds, every one
    assert got["ssd_decode_ms"] == pytest.approx(144 * 0.4)  # the stray call outside a run is not read
    need = 4 * granite_bytes.kernel_step_bytes(CFG, 30)  # the slice's rows beside the slice's time
    assert got["ssd_decode_roofline"] == pytest.approx(100 * need / 819e9 / 57.6e-3)
    assert 0 < got["ssd_decode_roofline"] < 100
    weights = 64 * 8 + 8 + 40 * 8 * 16 + 4 * 8 * 8 + 36 * 8 * 24  # the tied table ONCE, as the head
    step = granite_bytes.decode_step_bytes(cut)
    assert step == pytest.approx(weights + granite_bytes.state_step_bytes(CFG, 30))  # no KV yet
    assert got["granite_round_roofline"] == pytest.approx(100 * 4 * step / 819e9 / 0.100)
    assert 0 < got["granite_round_roofline"] < 100


def test_the_copy_reader_counts_copy_operations_inside_decode_rounds_alone():
    """`decode_copy_ms` reads any decode program's `copy*` operations (in this
    cell the int8 KV cache of heads of 64 re-laid out for the kernels and back),
    so it lists this cell alone by its entry and not by what it can read."""
    run = granite_run()
    assert reader("decode_copy_ms").read(run) is None  # kernels alone: nothing to read
    ops = run["_planes"][0][0][1]
    for r in range(10):
        for c, text in enumerate(("%copy.{} = s8[4,64,17,1024,64] copy(%p)",
                                  "%copy_bitcast_fusion.{} = bf16[64,8,64] fusion(%q)")):
            a = r * 120e6 + 90e6 + c * 2e6
            ops.append((text.format(r), a, a + (1.5e6, 0.25e6)[c]))
    ops.append(("%copy.77 = s8[4,64,17,1024,64] copy(%p)", 1310e6, 1312e6))  # outside any run
    ops.append(("%fusion.5 = bf16[64,8192] fusion(%copy.3)", 95e6, 96e6))  # no copy by its name
    assert reader("decode_copy_ms").read(run) == pytest.approx(1.75)
    assert reader("decode_copy_ms").read({"sut": run["sut"], "records": []}) is None  # an untraced run


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_gives_nothing_where_the_program_lacks_what_it_reads(name):
    """The parent commit given this cell's files, Olmo-Hybrid's cell (its
    kernel's name is `gdn_decode_step`, its table has no state-space sizes),
    decode_closed, a bare run, a window without a decode round, and the
    recorded v5e trace of decode_closed, which holds none of the kernel's events."""
    olmo = granite_run(kernel="gdn_decode_step")
    olmo["sut"]["gen"].cfg = get_config("olmo-hybrid-7b-d20")
    assert reader(name).read(olmo) is None
    dense = granite_run(kernel="decode_attn_q8_blocked")
    dense["sut"]["gen"].cfg = get_config("qwen3-8b")
    assert reader(name).read(dense) is None
    parent = granite_run(kernel="gdn_decode_step")  # a table from before the state-space fields
    parent["sut"]["gen"].cfg = SimpleNamespace(name="x", recurrent=True, n_experts=0, n_layers=20)
    assert reader(name).read(parent) is None
    bare = {"sut": {"gen": granite_run()["sut"]["gen"]}, "start": {}, "end": {}, "records": [],
            "window": (0.0, 1.0), "device": {"kind": "TPU v5 lite"}}
    assert reader(name).read(bare) is None
    idle = granite_run()
    idle["end"] = idle["start"]  # a window without a decode round
    idle["slice"]["rounds"] = []
    if name != "ssd_decode_ms":
        assert reader(name).read(idle) is None
    recorded = granite_run()
    path = os.path.join(ROOT, "benchmark", "fixtures", "v5e_decode_slice.xspace.txt")
    recorded["_planes"] = trace_reduce.read_planes(path)
    recorded["trace_reduced"] = trace_reduce.reduce_trace(path)
    # the kernel is not there, and its one run of the decode program is cut by the slice's edge:
    # no whole run, so no round's time either
    assert reader(name).read(recorded) is None


def test_the_other_hybrid_cells_readers_find_nothing_on_this_cell_and_keep_their_own():
    run = granite_run()
    for name in ("kda_decode_ms", "kda_decode_roofline", "solar_round_roofline",
                 "gdn_decode_ms", "gdn_decode_roofline"):
        assert reader(name).read(run) is None
    assert len({granite_bytes.KERNEL, olmo_hybrid_bytes.KERNEL, solar_bytes.KERNEL}) == 3


def test_the_configurations_file_is_its_catalog_row_whole():
    from benchmark import check_source

    config = json.load(open(FILE))
    rows = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "granite_catalog_row.jsonl")
    row = next(r for r in map(json.loads, open(rows)) if r["name"] == "granite-4.0-h-micro")
    assert check_source.differs(config, row) == []
    assert config["source"] == row["source_url"]
    assert config["reduced"] == [] and "published" not in config
    for key, value in row["config"].items():  # null for null, 0 for 0, the list whole
        assert key in config and type(config[key]) is type(value) and config[key] == value, key
    assert config["num_experts_per_tok"] == 0 and config["num_local_experts"] == 0
    assert config["rope_scaling"] is None and len(config["layer_types"]) == 40
    name, module = bench_run.load_reference(config)
    assert name == "granite_hybrid"
    # every path is compared; what is stated and held to nothing says why
    unheld = bench_run.check_sizes(config, CFG, module)
    assert [u.split(" ")[0] for u in unheld] == ["mamba_chunk_size", "max_position_embeddings", "model_type"]
    assert "block" in unheld[0]
    module.check(CFG)
    module.check(get_config("tiny-granite-hybrid"))
    for other in ("tiny-solar", "tiny-olmo-hybrid", "tiny-llm"):
        with pytest.raises(NotImplementedError):
            module.check(get_config(other))
    assert len(config["assumed"]) >= 6 and any("rope_theta" in a for a in config["assumed"])
    assert config["program"]["env"] == {"TPU_MODEL": "granite-4.0-h-micro", "TPU_KV_QUANT": "int8",
                                        "TPU_MAX_SLOTS": 64, "TPU_MAX_SEQ_LEN": 1024}
    assert config["reference_request"] == {"prompt_bytes": 200, "tokens": 16}
    expect = config["program"]["expect"]
    assert (expect["state_dtype"], expect["weights_dtype"]) == ("float32", "bfloat16")
    assert (expect["attn_impl"], expect["decode_impl"]) == ("pallas", "pallas")


@pytest.mark.parametrize("path,moved", [
    ("layer_types", ["mamba"] * 40), ("attention_multiplier", 0.125), ("embedding_multiplier", 1),
    ("residual_multiplier", 1.0), ("logits_scaling", 1), ("mamba_n_heads", 32), ("mamba_d_head", 128),
    ("mamba_d_state", 64), ("mamba_d_conv", 3), ("mamba_expand", 4), ("mamba_n_groups", 8),
    ("mamba_conv_bias", False), ("mamba_proj_bias", True), ("shared_intermediate_size", 4096),
    ("num_local_experts", 8), ("num_experts_per_tok", 2), ("position_embedding_type", "rope"),
    ("normalization_function", "layernorm"), ("intermediate_size", 4096), ("rope_theta", 500_000),
    ("tie_word_embeddings", False), ("num_key_value_heads", 4),
])
def test_a_key_of_the_file_that_is_not_the_programs_stops_the_run(path, moved):
    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    config[path] = moved
    with pytest.raises(AssertionError, match=path):
        bench_run.check_sizes(config, CFG, module)


def test_the_tables_name_every_key_run_py_does_not_hold_and_the_controls_move_the_logits():
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.models.llama import init_llama_params

    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    own = bench_run.own_paths()
    model = set(bench_run.model_paths(config))
    assert model - own == set(module.HELD) | set(module.ONLY) | set(module.STATED)
    assert {"attention_multiplier", "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "layer_types", "shared_intermediate_size"} <= set(module.HELD)
    assert {k for k in model if k.startswith("mamba_")} <= (
        set(module.HELD) | set(module.ONLY) | set(module.STATED))
    assert (module.ONLY["num_local_experts"], module.ONLY["position_embedding_type"]) == (0, "nope")
    assert set(module.STATED) == {"mamba_chunk_size"} and 0.05 < module.SERVED_TOL_REL < 0.5
    assert module.CONTROLS == ("int8", "fp8", "state_bf16", "lost_state")
    cfg = get_config("tiny-granite-hybrid")
    params = init_llama_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (32,), 3, 500))
    rows, cols = np.arange(24, 32), np.arange(cfg.vocab_size)
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
    assert 0.0 < moved["state_bf16"] < moved["int8"] < moved["fp8"] and moved["lost_state"] > moved["int8"]


ON_CELL = {*NEW, "decode_copy_ms", "decode_occupancy", "decode_round_ms", "engine_itl_p95_ms", "window_compiles.serve",
           "pallas_busy_share", "decode_token_yield", "engine_host_ms_per_round",
           "engine_event_gap_p95_ms", "stream_write_lag_p95_ms", "decode_attn_ms",
           "setup_first_dispatch_s.serve", "setup_first_dispatch_s.trace_lower",
           "setup_first_dispatch_s.backend", "state_pool_share", "event_gap_admit_share",
           "slot_vacant_ms", "slot_vacant_queued_ms"}  # what PR 41 put on the cell; a later metric may list it too
# PR 41 listed four more, which read an admit program of the window; since PR 42 this cell's
# prompts ride a decode round and none runs, every traced run read None, and PR 47 took the cell
# off their lists (they read in `kexaone_reason_closed`, whose rings rule the ride out)
NO_ADMIT_PROGRAM = {"admit_program_share", "admit_rows_mean", "admit_pad_waste_pct", "event_gap_admit_ms"}


def test_the_cell_is_the_hybrid_cells_traffic_number_for_number_and_its_entries_are_found_by_name(bench):
    traffic = os.path.join(ROOT, "benchmark", "traffic")
    mine = json.load(open(os.path.join(traffic, CELL + ".json")))
    assert mine == json.load(open(os.path.join(traffic, "olmo_hybrid_decode_closed.json")))
    assert mine == json.load(open(os.path.join(traffic, "solar_decode_closed.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-4.0-h-micro-bf16", CELL, 1)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == [] and config["file"] == os.path.relpath(FILE, ROOT)
    assert config["source"] == json.load(open(FILE))["source"]
    reports = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"itl_p95_ms", "out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in bench["per_layer"]}
    on_cell = {n for n, m in layer.items() if CELL in m.get("workloads", [CELL])}
    assert on_cell >= ON_CELL, ON_CELL - on_cell
    assert not on_cell & NO_ADMIT_PROGRAM
    for name in (*NEW, "decode_copy_ms"):  # its own entries, each on this cell alone
        assert layer[name]["workloads"] == [CELL] and layer[name]["moves"] == "out_tokens_per_s"
        mod = reader(name)
        assert (mod.NAME, mod.UNIT, mod.SOURCE, mod.LAYER) == (
            name, layer[name]["unit"], "device_trace", layer[name]["layer"])
    # no other cell's kernel metrics were put on this one
    for other in ("gdn_decode_ms", "gdn_decode_roofline", "olmo_round_roofline", "kda_decode_ms",
                  "kda_decode_roofline", "solar_round_roofline", "decode_round_roofline"):
        assert CELL not in layer[other]["workloads"]
