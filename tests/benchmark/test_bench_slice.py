"""A `device_trace` reader sets a count beside the traced slice's device times
and takes it from the slice's own rounds (PR 47). Until then the rows and the
live tokens were the whole 40 s window's and the times the 3 s slice's, and a
run the slice's edge had cut counted as a round: `gdn_decode_roofline` read
105.5% and `olmo_round_roofline` 106.1% (ledger, PR 46). Every `*_roofline`
reader, on one hand-made run a cell: a window whose rounds carry 64 rows and 64
streams, a slice whose plain rounds carry 32 and 32, and the slice's round time."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import counters, run as bench_run, trace_reduce  # noqa: E402
from llm_mcp_tpu.models.configs import get_config  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "v5e_decode_slice.xspace.txt")
MS = 1_000_000  # ns
# reader -> (the configuration whose cell lists it, the kernels its time is read from)
READERS = {
    "decode_round_roofline": ("qwen3-8b", ("decode_attn_q8_blocked",)),
    "decode_attn_roofline": ("qwen3-8b", ("decode_attn_q8_blocked",)),
    "kda_decode_roofline": ("solar-open2-250b-ep8", ("kda_decode_step",)),
    "solar_round_roofline": ("solar-open2-250b-ep8", ("kda_decode_step",)),
    "gdn_decode_roofline": ("olmo-hybrid-7b-d20", ("gdn_decode_step",)),
    "olmo_round_roofline": ("olmo-hybrid-7b-d20", ("gdn_decode_step",)),
    "ssd_decode_roofline": ("granite-4.0-h-micro", ("ssd_decode_step",)),
    "granite_round_roofline": ("granite-4.0-h-micro", ("ssd_decode_step",)),
    "kexaone_round_roofline": ("k-exaone-236b-ep8", ("decode_attn_win_q8", "decode_attn_q8_blocked")),
    "win_attn_roofline": ("k-exaone-236b-ep8", ("decode_attn_win_q8", "decode_attn_q8_blocked")),
    "full_attn_roofline": ("k-exaone-236b-ep8", ("decode_attn_win_q8", "decode_attn_q8_blocked")),
}


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def edge(steps: int, rows: int, touched: int, layers: int) -> dict:
    """The counters the readers take, at one edge: the observatory's sampled
    decode rounds, the expert layer's counts of `steps` decode steps, the cache
    by kind of layer."""
    counts = [[rows * steps, 2 * rows * steps, touched * steps, 9 * steps, steps] for _ in range(layers)]
    return {"perf": {
        "phases": {"decode": {"samples": steps // 4, "tokens": steps * rows}},
        "experts": {"counts": [counts, [[0] * 5] * layers], "held": 16, "router": 128},
        "kv_kinds": {"full": {"layers": 1, "bytes": 9, "positions": 1, "live_positions": 0},
                     "window": {"layers": 4, "bytes": 1, "positions": 1, "live_positions": 0}}}}


def traced_run(model: str, kernels, window_rows: int = 64, slice_rows: int = 32,
               window_touched: int = 12, slice_touched: int = 6) -> dict:
    """One cell's traced run by hand: ten runs of the decode program of 300 ms,
    the first and the last cut by the slice's edges to 120 ms, each whole one
    holding 40 calls of each kernel of 1 ms; `window_rows` streams of 500
    tokens live through the window, of which `slice_rows` reach the slice."""
    cfg = get_config(model)
    experts = max(getattr(cfg, "n_experts", 0), 1)
    layers = cfg.n_layers - getattr(cfg, "first_dense_layers", 0) if getattr(cfg, "n_experts", 0) else 1
    bank = np.zeros((layers, experts, 8, 8), np.int8)
    params = {"embed": np.zeros((64, 8), np.int8), "lm_head": np.zeros((8, 64), np.int8),
              "final_norm": np.zeros((8,), np.int8),
              "layers": {"w1e": bank, "w3e": bank, "w2e": bank, "wq": np.zeros((4, 1024, 1024), np.int8)}}
    gen = SimpleNamespace(cfg=cfg, params=params, kv_quant="int8", decode_chunk=4, max_slots=64)
    ops, mods = [], []
    for r in range(10):
        t0, cut = r * 310 * MS, r in (0, 9)
        mods.append(("jit_decode_chunk_fn(77)", t0, t0 + (120 if cut else 300) * MS))
        for k, kernel in enumerate(kernels):
            for c in range(16 if cut else 40):
                a = t0 + (1 + 2 * len(kernels) * c + 2 * k) * MS
                ops.append((f"%{kernel}.{c} = bf16[64,8,8,128] custom-call(...)", a, a + MS))
    steps = 4000
    streams = [{"status": 200, "error": None, "finish": "length", "sent": 0.0, "prompt_tokens": 500,
                "completion_tokens": 0, "events": [0.0, 100.0 if i < slice_rows else 20.0],
                "done": 100.0 if i < slice_rows else 20.0} for i in range(window_rows)]
    run = {"sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"}, "records": streams,
           "start": edge(0, window_rows, window_touched, layers),
           "end": edge(steps, window_rows, window_touched, layers), "window": (10.0, 50.0),
           "slice": {"start": edge(0, slice_rows, slice_touched, layers),
                     "end": edge(320, slice_rows, slice_touched, layers), "window": (26.0, 34.0),
                     "window_abs": (126.0, 134.0),
                     "rounds": [("decode", slice_rows, 126.0 + k) for k in range(8)]
                     + [("mixed", 64, 126.5 + k) for k in range(8)]},
           "_planes": ([(0, ops, mods)], {})}
    whole = [(b - a) / 1e9 for _n, a, b in trace_reduce.whole_runs(mods)]
    run["trace_reduced"] = {"module_runs": {"jit_decode_chunk_fn": [10, 0.264]},
                            "whole_runs": {"jit_decode_chunk_fn": [len(whole), sum(whole) / len(whole)]}}
    return run


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_roofline_reader_gives_the_slices_share(name):
    model, kernels = READERS[name]
    read = reader(name).read
    got = read(traced_run(model, kernels))
    assert got is not None and 0 < got < 100
    # the window's rows, streams and touched experts are no part of it ...
    assert read(traced_run(model, kernels, window_rows=40, window_touched=3)) == pytest.approx(got)
    assert read(traced_run(model, kernels, window_rows=64, window_touched=16)) == pytest.approx(got)
    # ... the slice's are: twice the rows and the streams in the slice move more bytes in its time
    fuller = read(traced_run(model, kernels, slice_rows=64))
    assert fuller > got * (1.9 if name.endswith(("decode_roofline", "attn_roofline")) else 1.0)
    # and the time is a WHOLE round's: the two runs the slice's edges cut (120 ms of 300, 16 kernel
    # calls of 40) would make the mean 264 ms and the share 114% of what it is
    run = traced_run(model, kernels)
    assert counters.decode_round_s(run) == pytest.approx(0.300)
    assert run["trace_reduced"]["module_runs"]["jit_decode_chunk_fn"][1] == pytest.approx(0.264)
    # a slice in which the engine dispatched no plain round, or an untraced run: nothing to read
    run["slice"]["rounds"] = [r for r in run["slice"]["rounds"] if r[0] != "decode"]
    if model not in ("qwen3-8b", "k-exaone-236b-ep8"):  # theirs count the streams, not a round's rows
        assert read(run) is None
    del run["slice"]
    assert read(run) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_no_roofline_reader_reads_a_cut_run_of_the_recorded_trace(name):
    """The recorded v5e slice holds the end of an admit program and the first
    1.4 ms of a decode round of 50 ms: read as a round, as every reader did
    until PR 47, it gives 36 times any roofline. No reader reads it now."""
    model, kernels = READERS[name]
    run = traced_run(model, kernels)
    run["_planes"] = trace_reduce.read_planes(FIXTURE)
    run["trace_reduced"] = trace_reduce.reduce_trace(FIXTURE)
    assert run["trace_reduced"]["module_runs"]["jit_decode_chunk_fn"][0] == 1
    got = reader(name).read(run)
    assert got is None or got <= 100.0
    assert got is None  # in this trace there is no whole run at all


def test_the_readers_above_are_every_roofline_entry_and_each_is_a_device_trace_metric(bench):
    entries = {m["name"]: m for m in bench["per_layer"] if m["name"].endswith("_roofline")}
    assert set(entries) >= set(READERS)
    for name in READERS:
        assert entries[name]["source"] == "device_trace" and reader(name).SOURCE == "device_trace"
    # a `program_counter` metric keeps the window: no reader of one asks for the slice
    folder = os.path.join(ROOT, "benchmark", "layer_metrics")
    for path in sorted(os.listdir(folder)):
        with open(os.path.join(folder, path)) as f:
            text = f.read()
        if '"program_counter"' in text:
            assert "slice_of" not in text, path


def test_the_four_admission_readers_list_the_one_cell_that_still_runs_admit_programs(bench):
    layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("admit_program_share", "admit_rows_mean", "admit_pad_waste_pct", "event_gap_admit_ms"):
        assert layer[name]["workloads"] == ["kexaone_reason_closed"], name
    # the one that reads 0.0 where no program runs, which is a reading, keeps every generation cell
    assert {"decode_closed", "solar_decode_closed", "olmo_hybrid_decode_closed", "granite_decode_closed",
            "kexaone_reason_closed"} <= set(layer["event_gap_admit_share"]["workloads"])
