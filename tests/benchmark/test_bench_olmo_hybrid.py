"""What PR 35 adds to the benchmark for the dense hybrid cell
`olmo_hybrid_decode_closed`: the configuration's file held to its row of the
catalog and to the program's table, the reference module's tables, the byte
functions against ISSUE 35's arithmetic, and the three readers on a hand-made
run: each gives its number from kernel names in the trace and the perf
observatory's phases, and None (so no entry in the result line) on a run
without them, as the parent commit's runs and every other configuration's are."""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import counters, olmo_hybrid_bytes, solar_bytes  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from llm_mcp_tpu.models.configs import get_config  # noqa: E402

NEW = ["gdn_decode_ms", "gdn_decode_roofline", "olmo_round_roofline"]
CELL = "olmo_hybrid_decode_closed"
CFG = get_config("olmo-hybrid-7b-d20")
FILE = os.path.join(ROOT, "benchmark", "configs", "olmo-hybrid-7b-d20-bf16.json")
STATE = 30 * 96 * 192 * 4  # a slot's float32 state of one linear layer
TAILS = 3 * 11_520 * 2  # and its convolution tails, bfloat16


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def phases(rounds: int, rows: int) -> dict:
    return {"phases": {"decode": {"samples": rounds, "tokens": rounds * rows * 4}}}


def olmo_run(kernel: str = "gdn_decode_step") -> dict:
    """Counters at both edges (100 sampled rounds of 60 rows), a trace with 10
    runs of the decode program of 100 ms, each holding 60 calls of the state
    kernel of 0.45 ms (the slice's edges cut the first and the last: eight
    whole runs), one request mid-stream for the whole window, and the traced
    slice as `run.measure` records it: the observatory took no sample inside
    it, as on the chip, and of the rounds the engine dispatched in it the plain
    ones carry 30 rows where the window's carry 60."""
    params = {"embed": np.zeros((64, 8), np.int8), "lm_head": np.zeros((8, 64), np.int8),
              "final_norm": np.zeros((8,), np.int8),
              "layers": {"w1": np.zeros((20, 8, 16), np.int8)},
              "gqa": {"wq": np.zeros((5, 8, 8), np.int8)},
              "kda": {"wqkv_lin": np.zeros((15, 8, 24), np.int8)}}
    gen = SimpleNamespace(cfg=CFG, params=params, kv_quant="int8", decode_chunk=4, max_slots=64)
    ops, mods = [], []
    for r in range(10):
        t0 = r * 120e6
        mods.append(("jit_decode_chunk_fn(77)", t0, t0 + 100e6))
        for c in range(60):
            a = t0 + 1e6 + c * 1.5e6
            ops.append((f"%{kernel}.{c} = (f32[64,3,5,384], f32[15,64,15,96,384]) custom-call(...)",
                        a, a + 0.45e6))
    ops.append((f"%{kernel}.99 = (f32[1]) custom-call(...)", 1300e6, 1301e6))  # outside any run
    record = {"status": 200, "error": None, "finish": "length", "prompt_tokens": 100,
              "completion_tokens": 400, "t_sent": 0.0, "t_first": 5.0, "t_last": 60.0, "t_done": 60.0}
    return {"sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"},
            "start": {"perf": phases(0, 60)}, "end": {"perf": phases(100, 60)},
            "records": [], "window": (10.0, 50.0), "_record": record,
            "slice": {"start": {"perf": phases(50, 60)}, "end": {"perf": phases(50, 60)},
                      "window": (26.0, 34.0), "window_abs": (126.0, 134.0),
                      "rounds": [("decode", 30, 126.5), ("mixed", 64, 128.0), ("decode", 30, 130.0)]},
            "trace_reduced": {"module_runs": {"jit_decode_chunk_fn": [10, 0.100]},
                              "whole_runs": {"jit_decode_chunk_fn": [8, 0.100]}},
            "_planes": ([(0, ops, mods)], {})}


def test_the_byte_functions_are_issue_35s_arithmetic():
    assert olmo_hybrid_bytes.linear_layers(CFG) == 15
    row = olmo_hybrid_bytes.kernel_row_bytes(CFG)
    assert row == 2 * STATE + 4 * (2 * 30 * 96 + 2 * 30 * 192 + 2 * 30)  # state twice, q k v o, decay, beta
    assert olmo_hybrid_bytes.kernel_step_bytes(CFG, 64) == 15 * 64 * row
    assert olmo_hybrid_bytes.state_step_bytes(CFG, 64) == 15 * 64 * (row + 2 * TAILS)
    assert round(15 * 64 * 2 * STATE / 1e9, 2) == 4.25  # "64 x 66.4 MB = 4.25 GB a step"
    assert solar_bytes.kv_row_bytes(CFG, "int8") == 5 * 30 * 2 * (128 + 2) == 39_000
    # the pool as the engine allocates it: its logical bytes, nothing padded
    assert round((15 * 64 * (STATE + TAILS)) / 1e9, 2) == 2.19


def test_the_pool_the_program_allocates_holds_its_logical_bytes():
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.executor.memory import build_state_pool
    from llm_mcp_tpu.models.kda import init_kda_state

    state = jax.eval_shape(lambda: init_kda_state(CFG, 15, 64, jnp.bfloat16))
    assert state["S"].shape == (15, 64, 15, 96, 384) and state["conv"].shape == (15, 64, 3 * 11_520)
    pool = build_state_pool(CFG, 64, state, SimpleNamespace(info=lambda *a: None))
    stats = pool.stats(live_slots=32)
    assert stats["bytes"] == 15 * 64 * 30 * 96 * 192 * 4 + 15 * 64 * TAILS
    assert stats["bytes_per_slot"] == 15 * (STATE + TAILS) and stats["live_bytes"] * 2 == stats["bytes"]
    assert stats["layout"] == {"S": [15, 64, 15, 96, 384], "conv": [15, 64, 34_560]}
    assert all(d % 128 == 0 for d in (stats["layout"]["S"][-1], stats["layout"]["conv"][-1]))


def test_each_new_reader_gives_its_number_on_a_run_with_the_kernel_and_the_phases():
    run = olmo_run()
    got = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    cut = counters.slice_of(run)
    assert olmo_hybrid_bytes.live_rows(run) == pytest.approx(60.0)  # the window's sampled rounds
    assert olmo_hybrid_bytes.live_rows(cut) == pytest.approx(30.0)  # the slice's plain rounds, every one
    assert got["gdn_decode_ms"] == pytest.approx(60 * 0.45)  # the stray call outside a run is not read
    need = 4 * olmo_hybrid_bytes.kernel_step_bytes(CFG, 30)  # the slice's rows beside the slice's time
    assert got["gdn_decode_roofline"] == pytest.approx(100 * need / 819e9 / 27e-3)
    assert 0 < got["gdn_decode_roofline"] < 100
    weights = 8 * 64 + 8 + 20 * 8 * 16 + 5 * 8 * 8 + 15 * 8 * 24  # head, norm, w1, wq, wqkv_lin
    step = olmo_hybrid_bytes.decode_step_bytes(cut)
    assert step == pytest.approx(weights + olmo_hybrid_bytes.state_step_bytes(CFG, 30))  # no KV yet
    assert got["olmo_round_roofline"] == pytest.approx(100 * 4 * step / 819e9 / 0.100)
    assert 0 < got["olmo_round_roofline"] < 100
    del run["slice"]  # an untraced run: no device time to set a count beside
    assert reader("gdn_decode_roofline").read(run) is None and reader("olmo_round_roofline").read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_gives_nothing_where_the_program_lacks_what_it_reads(name):
    """The parent commit given this cell's files, Solar's cell (its kernel keeps
    the name `kda_decode_step`, its feed-forward is experts), decode_closed."""
    solar = olmo_run(kernel="kda_decode_step")
    solar["sut"]["gen"].cfg = get_config("solar-open2-250b-ep8")
    assert reader(name).read(solar) is None
    dense = olmo_run(kernel="decode_attn_q8_blocked")
    dense["sut"]["gen"].cfg = get_config("qwen3-8b")
    assert reader(name).read(dense) is None
    bare = {"sut": {"gen": olmo_run()["sut"]["gen"]}, "start": {}, "end": {}, "records": [],
            "window": (0.0, 1.0), "device": {"kind": "TPU v5 lite"}}
    assert reader(name).read(bare) is None
    idle = olmo_run()
    idle["end"] = idle["start"]  # a window without a decode round
    idle["slice"]["rounds"] = []
    if name != "gdn_decode_ms":
        assert reader(name).read(idle) is None


def test_solars_readers_find_nothing_on_this_cell_and_keep_their_own():
    """`solar_bytes.live_rows` takes a step's rows from the expert counters: on
    a dense configuration Solar's rooflines read None, which is why this cell
    brings its own; `kda_decode_ms` reads the kernel by Solar's name only."""
    run = olmo_run()
    assert solar_bytes.live_rows(run) is None
    for name in ("kda_decode_ms", "kda_decode_roofline", "solar_round_roofline"):
        assert reader(name).read(run) is None
    assert olmo_hybrid_bytes.KERNEL == "gdn_decode_step" != solar_bytes.KERNEL == "kda_decode_step"


def test_the_configurations_file_is_its_catalog_row_cut_to_its_first_twenty_layers():
    from benchmark import check_source

    config = json.load(open(FILE))
    rows = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "catalog_rows.jsonl")
    row = next(r for r in map(json.loads, open(rows)) if r["name"] == "Olmo-Hybrid-7B")
    assert check_source.differs(config, row) == []
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["layer_types", "num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32, "layer_types": row["config"]["layer_types"]}
    assert config["layer_types"] == row["config"]["layer_types"][:20] and config["num_hidden_layers"] == 20
    for width in ("hidden_size", "intermediate_size", "linear_key_head_dim", "linear_value_head_dim",
                  "num_attention_heads", "num_key_value_heads", "vocab_size"):
        assert config[width] == row["config"][width]
    assert config["rope_parameters"] == {"rope_theta": None}
    name, module = bench_run.load_reference(config)
    assert name == "olmo_hybrid"
    assert bench_run.check_sizes(config, CFG, module) == ["max_position_embeddings", "model_type"]
    module.check(CFG)
    module.check(get_config("tiny-olmo-hybrid"))
    for other in ("tiny-solar", "tiny-v2", "tiny-llm"):
        with pytest.raises(NotImplementedError):
            module.check(get_config(other))
    assert len(config["assumed"]) >= 6 and any("norm placement" in a for a in config["assumed"])
    assert config["program"]["env"] == {"TPU_MODEL": "olmo-hybrid-7b-d20", "TPU_KV_QUANT": "int8",
                                        "TPU_MAX_SLOTS": 64, "TPU_MAX_SEQ_LEN": 1024}
    assert config["reference_request"] == {"prompt_bytes": 200, "tokens": 16}


@pytest.mark.parametrize("path,moved", [
    ("layer_types", ["full_attention"] * 20), ("linear_key_head_dim", 128),
    ("linear_value_head_dim", 96), ("linear_num_value_heads", 15), ("linear_conv_kernel_dim", 3),
    ("linear_allow_neg_eigval", False), ("num_key_value_heads", 6), ("intermediate_size", 8192),
])
def test_a_key_of_the_file_that_is_not_the_programs_stops_the_run(path, moved):
    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    config[path] = moved
    with pytest.raises(AssertionError, match=path):
        bench_run.check_sizes(config, CFG, module)


def test_a_rope_theta_is_refused_and_the_published_list_is_held():
    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    config["rope_parameters"] = {"rope_theta": 500_000.0}
    with pytest.raises(AssertionError, match="rope_parameters.rope_theta"):
        bench_run.check_sizes(config, CFG, module)
    config = json.load(open(FILE))
    config["published"]["layer_types"] = config["published"]["layer_types"][::-1]
    with pytest.raises(AssertionError, match="published.layer_types"):
        bench_run.check_sizes(config, CFG, module)


@pytest.mark.parametrize("attr,lowered", [("state_dtype", "bfloat16"), ("weights_dtype", "int8")])
def test_a_program_in_a_lower_precision_than_the_file_states_is_not_correct(monkeypatch, attr, lowered):
    """Greedy tokens cannot tell a bfloat16 state or int8 weights from what the
    file states, so the file holds the two precisions itself (`program.expect`):
    run.py's comparison refuses an engine that reports another."""
    from benchmark import correctness

    config = json.load(open(FILE))
    stated = dict(config["program"]["expect"])
    assert (stated["state_dtype"], stated["weights_dtype"]) == ("float32", "bfloat16")
    assert (stated["attn_impl"], stated["decode_impl"]) == ("pallas", "pallas")
    gen = SimpleNamespace(max_seq_len=1024, **stated)
    monkeypatch.setattr(correctness, "served_tokens", lambda *a: ([1, 2], [3]))
    monkeypatch.setattr(correctness, "hold_to_reference", lambda *a: {"worst_regret_rel": 0.0})
    run = {"sut": {"gen": gen, "port": 0, "model": "m", "reference": ("olmo_hybrid", None)},
           "args": SimpleNamespace(seed=7), "spec": {"config": config},
           "end": {"reference_falls": {}}}
    assert correctness.check_generation(run)["worst_regret_rel"] == 0.0
    setattr(gen, attr, lowered)
    with pytest.raises(AssertionError, match=f"{attr}='{lowered}', the configuration states"):
        correctness.check_generation(run)
    setattr(gen, attr, stated[attr])
    run["end"]["reference_falls"] = {"gdn_decode_step": 60}
    with pytest.raises(AssertionError, match="fell to reference math"):
        correctness.check_generation(run)


ON_CELL = {*NEW, "decode_occupancy", "decode_token_yield", "engine_host_ms_per_round",
           "decode_round_ms", "engine_itl_p95_ms", "engine_event_gap_p95_ms",
           "stream_write_lag_p95_ms", "window_compiles.serve", "pallas_busy_share",
           "decode_attn_ms", "setup_first_dispatch_s.serve",
           "setup_first_dispatch_s.trace_lower", "setup_first_dispatch_s.backend",
           "state_pool_share"}  # the seventeen PR 35 put on the cell; a later metric may list it too


def test_the_cell_is_solars_traffic_number_for_number_and_its_entries_are_found_by_name(bench):
    """The cell, its configuration and its three metrics by NAME, wherever they
    stand in their lists: nothing here is said of another configuration's
    entries, of what comes last, or of metrics added to the cell since."""
    traffic = os.path.join(ROOT, "benchmark", "traffic")
    assert json.load(open(os.path.join(traffic, CELL + ".json"))) == json.load(
        open(os.path.join(traffic, "solar_decode_closed.json")))
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmo-hybrid-7b-d20-bf16", CELL, 1)
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["layer_types", "num_hidden_layers"]
    reports = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"itl_p95_ms", "out_tokens_per_s", "setup_s"}
    layer = {m["name"]: m for m in bench["per_layer"]}
    on_cell = {n for n, m in layer.items() if CELL in m.get("workloads", [CELL])}
    assert on_cell >= ON_CELL, ON_CELL - on_cell
    for name in NEW:  # its three own entries, each on this cell alone
        assert layer[name]["workloads"] == [CELL] and layer[name]["moves"] == "out_tokens_per_s"
        mod = reader(name)
        assert (mod.NAME, mod.UNIT, mod.SOURCE) == (name, layer[name]["unit"], "device_trace")


def test_the_references_controls_move_the_logits_and_the_tables_name_every_key_run_py_does_not_hold():
    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.models.llama import init_llama_params

    config = json.load(open(FILE))
    _, module = bench_run.load_reference(config)
    own = bench_run.own_paths()
    model = set(bench_run.model_paths(config))
    assert model - own == (set(module.HELD) | set(module.ONLY)) - {
        "published.layer_types", "published.num_hidden_layers"}
    assert module.STATED == {} and 0.05 < module.SERVED_TOL_REL < 0.5
    assert module.CONTROLS == ("int8", "fp8", "state_bf16", "lost_state")
    cfg = get_config("tiny-olmo-hybrid")
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
    assert moved["state_bf16"] < moved["int8"] < moved["fp8"] and moved["lost_state"] > moved["int8"]
    assert moved["state_bf16"] > 0.0
