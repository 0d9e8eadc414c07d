"""The per-layer readers of PR 24 on hand-made runs: each gives its value
from the program's span, counter or name, and nothing (None, so no entry in
the result line) where the program has none, as the parent commit has not.
The two that read the trace are also held to a recorded slice of a v5e trace
in test_bench_trace_phases.py."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import counters, run as bench_run, spans  # noqa: E402

W = (100.0, 140.0)


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def ring_of(events):
    return SimpleNamespace(snapshot=lambda etype="": [
        {"etype": etype, "fields": f} for e, f in events if e == etype])


def gen_with(events=(), samples=None, decode_chunk=4):
    perf = SimpleNamespace() if samples is None else SimpleNamespace(samples=lambda kind: samples.get(kind, []))
    return SimpleNamespace(_flight=ring_of(list(events)), _perf=perf, decode_chunk=decode_chunk)


def test_decode_token_yield_is_delivered_over_row_steps_in_the_window():
    events = [("emit", {"rid": 1, "rows": 32, "delivered": 128, "t": 99.9}),  # before the window
              ("emit", {"rid": 2, "rows": 32, "delivered": 120, "t": 100.0}),
              ("emit", {"rid": 3, "rows": 30, "delivered": 97, "t": 139.9}),
              ("emit", {"rid": 4, "rows": 8, "delivered": 32, "t": 140.0}),  # after it
              ("fetch", {"rid": 2, "wait_ms": 120.0, "t": 100.0}),
              ("emit", None)]  # an event with no fields never raises
    run = {"sut": {"gen": gen_with(events)}, "window_abs": W}
    assert reader("decode_token_yield").read(run) == pytest.approx(100.0 * (120 + 97) / ((32 + 30) * 4))
    # the parent's ring holds dispatches only, and no `t`
    old = {"sut": {"gen": gen_with([("decode", {"rid": 2, "rows": 32})])}, "window_abs": W}
    assert reader("decode_token_yield").read(old) is None


@pytest.mark.parametrize("name,kind", [("engine_event_gap_p95_ms", "event_gap"),
                                       ("stream_write_lag_p95_ms", "stream_lag")])
def test_sample_readers_cut_by_the_window_and_take_the_p95(name, kind):
    inside = [(100.0 + 0.3 * k, 0.130 + 0.001 * (k % 7)) for k in range(100)]
    inside[50] = (inside[50][0], 0.270)
    samples = {kind: [(99.0, 9.0)] + inside + [(141.0, 9.0)]}
    run = {"sut": {"gen": gen_with(samples=samples)}, "window_abs": W}
    vals = sorted(v for _t, v in inside)
    want = vals[94] + (vals[95] - vals[94]) * 0.05  # linear between closest ranks, as reduce.percentile
    assert reader(name).read(run) == pytest.approx(1e3 * want)
    assert 130.0 <= reader(name).read(run) < 140.0  # whole gaps of about a round: not divided by tokens
    assert reader(name).read({"sut": {"gen": gen_with(samples={})}, "window_abs": W}) is None
    assert reader(name).read({"sut": {"gen": gen_with(samples=None)}, "window_abs": W}) is None  # the parent


PARTS = {"serve": {"entries": 14, "wall_s": 52.5, "trace_s": 6.25, "lower_s": 4.0, "backend_s": 30.5,
                   "cache_load_s": 28.0, "compile_requests": 31.0},
         "warmup": {"entries": 40, "wall_s": 300.0, "trace_s": 50.0, "lower_s": 40.0, "backend_s": 200.0,
                    "cache_load_s": 0.0, "compile_requests": 40.0}}


@pytest.mark.parametrize("part,want", [("serve", 52.5), ("trace_lower", 10.25), ("backend", 30.5)])
def test_setup_first_dispatch_reads_the_serve_threads_parts_at_the_windows_start(part, want):
    run = {"start": {"ledger": {"entries": 54, "parts": PARTS}},
           "end": {"ledger": {"entries": 54, "parts": {"serve": dict(PARTS["serve"], wall_s=99.0)}}}}
    r = reader(f"setup_first_dispatch_s.{part}")
    assert r.read(run) == want
    assert r.read({"start": {"ledger": {"entries": 54, "by_src": {"serve": 14}}}}) is None  # the parent's ledger
    assert r.read({"start": {"ledger": {"entries": 0, "parts": {}}}}) is None  # no first dispatch yet
    assert r.read({"start": {}}) is None  # an embedding cell


def test_embed_host_locked_is_the_mean_over_the_windows_forwards():
    recent = [(99.0, 1.05, 9.0), (101.0, 1.05, 0.012), (120.0, 1.06, 0.020), (139.0, 1.05, 0.010), (140.5, 1.0, 9.0)]
    emb = SimpleNamespace(stats=lambda: {"forwards": 5, "recent": recent})
    run = {"sut": {"emb": emb}, "window_abs": W}
    assert reader("embed_host_locked_ms").read(run) == pytest.approx(14.0)
    assert reader("embed_host_locked_ms").read({"sut": {"emb": SimpleNamespace()}, "window_abs": W}) is None
    empty = SimpleNamespace(stats=lambda: {"forwards": 0, "recent": []})
    assert reader("embed_host_locked_ms").read({"sut": {"emb": empty}, "window_abs": W}) is None


# -- the two that read the trace, on planes made by hand ------------------------

MS = 1_000_000  # ns
KERNEL = ('%decode_attn_q8_blocked.5 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)} custom-call(s32[1]{0} %x), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
OTHER_ARM = KERNEL.replace("decode_attn_q8_blocked.5", "decode_attn_q8_whole.7")
APPEND = KERNEL.replace("decode_attn_q8_blocked.5", "append_kv_q8.9")


def planes_by_hand(named=True, annotated=True):
    """Two runs of the decode program of 130 ms between an admit the slice's
    start cut and one its end cut (a per-run mean leaves both out:
    `trace_reduce.whole_runs`); attention kernels of 2 + 1 ms in the first run,
    3 ms in the second, 5 ms of them in the admit program (not counted); the
    engine thread's phases around them."""
    k1, k2 = (KERNEL, OTHER_ARM) if named else (KERNEL.replace("decode_attn_q8_blocked", "branch_1_fun"),) * 2
    ops = [(k1, 10 * MS, 12 * MS), (k2, 20 * MS, 21 * MS), (APPEND, 30 * MS, 31 * MS),
           ("%fusion.1 = bf16[32,4096]{1,0} fusion(bf16[32,4096]{1,0} %decode_attn_q8_blocked.5)", 40 * MS, 90 * MS),
           (k1, 150 * MS, 153 * MS), (k1, 290 * MS, 295 * MS)]
    mods = [("jit_admit_fn(2)", -9 * MS, -1 * MS),
            ("jit_decode_chunk_fn(1)", 0, 130 * MS), ("jit_decode_chunk_fn(1)", 140 * MS, 270 * MS),
            ("jit_admit_fn(2)", 280 * MS, 300 * MS)]
    host = {"python3": [("np.asarray(jax.Array)", 0, 125 * MS)]}
    if annotated:
        host["engine-loop/1234"] = [
            ("engine.dispatch", 0, 2 * MS), ("engine.emit", 2 * MS, 5 * MS),
            ("engine.admit", 5 * MS, 25 * MS), ("engine.admit.sync", 6 * MS, 24 * MS),
            ("engine.fetch", 25 * MS, 130 * MS), ("engine.fetch.sync", 25 * MS, 129 * MS),
            ("engine.dispatch", 130 * MS, 131 * MS), ("engine.prefill", 131 * MS, 132 * MS),
            ("engine.idle", 6 * MS, 24 * MS)]
    return [(0, ops, mods)], host


def test_engine_host_ms_per_round_is_the_phases_less_their_syncs_over_the_rounds():
    run = {"_planes": planes_by_hand()}
    # dispatch 2 + 1, emit 3, admit 20 - 18, prefill 1 = 9 ms over 2 rounds; fetch is waiting, not work
    assert reader("engine_host_ms_per_round").read(run) == pytest.approx(4.5)
    assert reader("engine_host_ms_per_round").read({"_planes": planes_by_hand(annotated=False)}) is None
    assert reader("engine_host_ms_per_round").read({"trace": {}}) is None  # an untraced run


def test_decode_attn_readers_sum_the_arms_inside_the_decode_program():
    run = {"_planes": planes_by_hand()}
    assert spans.decode_attn_s(run) == pytest.approx((2 + 1 + 3) / 2 / 1e3)
    assert reader("decode_attn_ms").read(run) == pytest.approx(3.0)
    total, rounds, found = spans.kernel_seconds(run["_planes"][0], "jit_decode_chunk_fn", "decode_attn")
    assert (rounds, found) == (2, {"decode_attn_q8_blocked", "decode_attn_q8_whole"})
    # the parent's kernel is `branch_1_fun`: nothing to read
    old = {"_planes": planes_by_hand(named=False)}
    assert reader("decode_attn_ms").read(old) is None and reader("decode_attn_roofline").read(old) is None


def test_decode_attn_roofline_is_live_kv_bytes_over_the_peak_over_the_kernels_time():
    from benchmark import peaks

    cfg = SimpleNamespace(n_kv_heads=8, n_layers=36, resolved_head_dim=128)
    gen = SimpleNamespace(cfg=cfg, kv_quant="int8", decode_chunk=4)
    # one stream with 6,000 cached tokens through the whole window
    rec = {"status": 200, "done": 45.0, "finish": "length", "events": [5.0, 45.0], "prompt_tokens": 6000,
           "completion_tokens": 0}
    run = {"_planes": planes_by_hand(), "sut": {"gen": gen}, "device": {"kind": "TPU v5 lite"},
           "records": [rec], "window": (10.0, 40.0), "slice": {"window": (21.0, 29.0)}}
    row = peaks.kv_row_bytes(cfg, "int8")
    assert row == 36 * 8 * 2 * 130
    least_s = 4 * row * 6000 / 819e9
    assert reader("decode_attn_roofline").read(run) == pytest.approx(100.0 * least_s / 0.003)
    assert 0 < reader("decode_attn_roofline").read(run) < 100
    # the live tokens are the SLICE's, whose kernels these are: a second stream that ends before
    # the slice is in the window's mean and not in the reading
    run["records"].append(dict(rec, events=[5.0, 20.0], done=20.0))
    assert counters.mean_live_tokens(run) > 1.3 * 6000
    assert reader("decode_attn_roofline").read(run) == pytest.approx(100.0 * least_s / 0.003)
    del run["slice"]  # an untraced run's records have no device time beside them
    assert reader("decode_attn_roofline").read(run) is None
