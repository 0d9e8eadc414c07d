"""The readers PR 32 adds for the hybrid (recurrent-state, expert-share) cell,
on a hand-made run: each gives its number from the state pool's block of
`perf_stats()` and from kernel names in the trace, and None (so no entry in the
result line) on a run without them, as the parent commit's runs and every other
configuration's are."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import counters, run as bench_run, solar_bytes  # noqa: E402
from llm_mcp_tpu.models.configs import get_config  # noqa: E402

NEW = ["solar_round_roofline", "kda_decode_ms", "kda_decode_roofline",
       "moe_local_pairs_per_row", "moe_load_max_over_mean", "state_pool_share"]
CFG = get_config("solar-open2-250b-ep8")
STEPS = 1000  # decode steps in the window
# a step routes 60 rows a layer; 61 pairs land here on 31 experts, the fullest takes 5
A_STEP = [60, 61, 31, 5, 1]


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def pool_block(live: int) -> dict:
    per_slot = 3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    return {"bytes": 64 * per_slot, "bytes_per_slot": per_slot, "slots": 64, "live_slots": live,
            "live_bytes": live * per_slot, "off": {}, "admitted_total": 0}


def experts_block(steps: int) -> dict:
    counts = [[[c * steps for c in A_STEP] for _ in range(4)], [[0] * 5 for _ in range(4)]]
    return {"counts": counts, "held": 40, "router": 320}


def solar_run() -> dict:
    """Counters at both edges, a trace with 10 runs of the decode program of
    40 ms, each holding 12 calls of the state kernel of 0.8 ms (the slice's
    edges cut the first and the last: eight whole runs), and the traced slice
    as `run.measure` records it: the counters at ITS edges and the rounds the
    engine dispatched in it. The window's steps carry 60 rows; the slice's
    plain rounds carry 30, and a mixed round between them its own 64."""
    bank = np.zeros((4, 40, 8, 8), np.int8)  # shapes stand in: bytes are what is read
    params = {"embed": np.zeros((64, 8), np.int8), "lm_head": np.zeros((8, 64), np.int8),
              "final_norm": np.zeros((8,), np.int8),
              "layers": {"w1e": bank, "w3e": bank, "w2e": bank, "router": np.zeros((4, 8, 320), np.int8)},
              "gqa": {"wq": np.zeros((1, 8, 8), np.int8)}, "kda": {"wqkv_lin": np.zeros((3, 8, 24), np.int8)}}
    gen = SimpleNamespace(cfg=CFG, params=params, kv_quant="int8", decode_chunk=4, max_slots=64)
    ops, mods = [], []
    for r in range(10):
        t0 = r * 50e6
        mods.append(("jit_decode_chunk_fn(77)", t0, t0 + 40e6))
        for c in range(12):
            a = t0 + 1e6 + c * 3e6
            ops.append((f"%kda_decode_step.{c} = (f32[64,64,128], f32[3,64,64,128,128]) custom-call(...)",
                        a, a + 0.8e6))
    ops.append(("%kda_decode_step.99 = (f32[1]) custom-call(...)", 600e6, 601e6))  # outside any run
    return {"sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"},
            "start": {"perf": {"state_pool": pool_block(62), "experts": experts_block(0)}},
            "end": {"perf": {"state_pool": pool_block(64), "experts": experts_block(STEPS)}},
            "records": [], "window": (10.0, 50.0),
            "slice": {"start": {"perf": {"experts": experts_block(STEPS // 2)}},
                      "end": {"perf": {"experts": experts_block(STEPS // 2 + 12)}},
                      "window": (26.0, 34.0), "window_abs": (126.0, 134.0),
                      "rounds": [("decode", 30, 126.5), ("mixed", 64, 128.0), ("decode", 30, 130.0)]},
            "trace_reduced": {"module_runs": {"jit_decode_chunk_fn": [10, 0.040]},
                              "whole_runs": {"jit_decode_chunk_fn": [8, 0.040]}},
            "_planes": ([(0, ops, mods)], {})}


def test_each_new_reader_gives_its_number_on_a_run_with_the_counters():
    run = solar_run()
    got = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    assert got["moe_local_pairs_per_row"] == pytest.approx(61 / 60 / (8 * 40 / 320))
    assert got["moe_load_max_over_mean"] == pytest.approx(5 / (61 / 40))
    assert got["state_pool_share"] == pytest.approx(100 * (62 + 64) / 2 / 64)
    assert got["kda_decode_ms"] == pytest.approx(12 * 0.8)  # the stray call outside a run is not read
    # a step: three layers, and the 30 rows of the SLICE's plain rounds, whose time this is; not
    # the window's 60, nor the mixed round's 64
    state = 3 * 30 * (2 * 64 * 128 * 128 * 4 + 6 * 64 * 128 * 4)
    assert got["kda_decode_roofline"] == pytest.approx(100 * 4 * state / 819e9 / 9.6e-3)
    assert 0 < got["kda_decode_roofline"] < 100
    # the round: weights outside the banks once a step, 31 of 40 experts a layer, state, no KV yet
    cut = counters.slice_of(run)
    step = solar_bytes.decode_step_bytes(cut)
    one_expert = 3 * 8 * 8
    weights = 64 * 8 + 8 + 4 * 8 * 320 + 8 * 8 + 3 * 8 * 24  # head, norm, router, wq, wqkv_lin
    assert step == pytest.approx(weights + 4 * 31 * one_expert + solar_bytes.state_step_bytes(CFG, 30))
    assert solar_bytes.decode_step_bytes(run) == pytest.approx(
        weights + 4 * 31 * one_expert + solar_bytes.state_step_bytes(CFG, 60))  # the window's, no reader's
    assert got["solar_round_roofline"] == pytest.approx(100 * 4 * step / 819e9 / 0.040)
    assert 0 < got["solar_round_roofline"] < 100
    del run["slice"]  # an untraced run: no device time to set a count beside
    assert reader("kda_decode_roofline").read(run) is None and reader("solar_round_roofline").read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_gives_nothing_on_a_cell_without_the_counters(name):
    """decode_closed at the parent, or at this commit: `perf_stats()` has no
    `state_pool` block and the trace no such kernel."""
    run = solar_run()
    run["start"], run["end"] = {"perf": {"phases": {}}}, {"perf": {"phases": {}}}
    run["slice"].update(start=run["start"], end=run["end"])
    run["_planes"] = ([(0, [("%decode_attn_q8_blocked.1 = custom-call(...)", 1e6, 2e6)],
                        [("jit_decode_chunk_fn(77)", 0.0, 40e6)])], {})
    assert reader(name).read(run) is None
    assert reader(name).read({"sut": {"gen": run["sut"]["gen"]}, "start": {}, "end": {},
                              "records": [], "window": (0.0, 1.0), "device": run["device"]}) is None


def test_a_window_without_a_decode_step_gives_nothing():
    run = solar_run()
    run["end"]["perf"].update(state_pool=pool_block(64), experts=experts_block(0))
    run["slice"].update(start=run["start"], end=run["end"], rounds=[])
    for name in ("solar_round_roofline", "kda_decode_roofline", "moe_local_pairs_per_row",
                 "moe_load_max_over_mean"):
        assert reader(name).read(run) is None
    assert reader("state_pool_share").read(run) is not None


def test_the_configurations_file_is_its_catalog_row_with_the_cut_the_issue_names():
    import json

    from benchmark import check_source

    path = os.path.join(ROOT, "benchmark", "configs", "solar-open2-250b-ep8-bf16.json")
    config = json.load(open(path))
    rows = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "catalog_rows.jsonl")
    row = next(r for r in map(json.loads, open(rows)) if r["name"] == "Solar-Open2-250B")
    assert check_source.differs(config, row) == []
    assert config["reduced"] == ["gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"gqa_layers": row["config"]["gqa_layers"], "n_routed_experts": 320,
                                   "num_hidden_layers": 48, "vocab_size": 196608}
    name, module = bench_run.load_reference(config)
    assert name == "solar_open2"
    assert bench_run.check_sizes(config, CFG, module) == ["max_position_embeddings", "model_type"]
    module.check(CFG)
    with pytest.raises(NotImplementedError):
        module.check(get_config("tiny-v2"))


@pytest.mark.parametrize("attr,lowered", [("state_dtype", "bfloat16"), ("expert_dtype", "int8")])
def test_a_program_in_a_lower_precision_than_the_file_states_is_not_correct(monkeypatch, attr, lowered):
    """Greedy tokens cannot tell a bfloat16 state or int8 expert banks from
    what the file states (`references/solar_open2.py`: both controls pass any
    limit the program passes), so the file holds the two precisions itself:
    run.py's comparison refuses an engine that reports another."""
    import json

    from benchmark import correctness

    path = os.path.join(ROOT, "benchmark", "configs", "solar-open2-250b-ep8-bf16.json")
    config = json.load(open(path))
    assert config["program"]["expect"]["state_dtype"] == "float32"
    assert config["program"]["expect"]["expert_dtype"] == "bfloat16"
    stated = dict(config["program"]["expect"])
    gen = SimpleNamespace(max_seq_len=1024, **stated)
    monkeypatch.setattr(correctness, "served_tokens", lambda *a: ([1, 2], [3]))
    monkeypatch.setattr(correctness, "hold_to_reference", lambda *a: {"worst_regret_rel": 0.0})
    run = {"sut": {"gen": gen, "port": 0, "model": "m", "reference": ("solar_open2", None)},
           "args": SimpleNamespace(seed=7), "spec": {"config": config},
           "end": {"reference_falls": {}}}
    assert correctness.check_generation(run)["worst_regret_rel"] == 0.0
    setattr(gen, attr, lowered)
    with pytest.raises(AssertionError, match=f"{attr}='{lowered}', the configuration states"):
        correctness.check_generation(run)
