"""The trace reduction on a small recorded trace: 8 ms cut from a v5e trace
of the decode_closed cell (PR 23), the end of a `jit_admit_fn`, 4.3 ms with
nothing on the device, the start of a `jit_decode_chunk_fn`. Operations across
the cut were clipped to it."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "v5e_decode_slice.xspace.txt")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(FIXTURE)


def test_busy_is_the_union_of_operation_intervals(reduced):
    chips, _host = tr.read_planes(FIXTURE)
    (_idx, ops, mods), = chips
    assert [m[0].split("(")[0] for m in mods] == ["jit_admit_fn", "jit_decode_chunk_fn"]
    # the step programs' own spans say when the device worked: 2.303 ms + 1.382 ms
    by_module = sum(b - a for _n, a, b in mods) / 1e9
    assert reduced["busy_s"] == pytest.approx(by_module, rel=0.01)
    assert reduced["busy_s"] == pytest.approx(0.003685, rel=0.01)
    assert reduced["window_s"] == pytest.approx(0.008, rel=0.001)
    # containers (while, conditional) span their bodies: a sum would count twice
    assert sum(b - a for _n, a, b in ops) / 1e9 > 1.5 * reduced["busy_s"]
    assert reduced["chips"] == 1


def test_idle_gap_is_named_by_the_programs_around_it_and_the_host(reduced):
    (name, seconds), = [g for g in reduced["idle_gaps"] if g[1] > 1e-3]
    assert seconds == pytest.approx(0.004315, rel=0.01)
    assert name.startswith("jit_admit_fn -> jit_decode_chunk_fn")
    assert "python3:" in name
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.5394, abs=0.005)


def test_time_by_name_counts_leaves_and_knows_kernels(reduced):
    names = dict(map(tuple, reduced["device_ops"]))
    assert not any(tr.CONTAINERS.match(n.split(" ")[0]) for n in names)
    assert sum(names.values()) <= reduced["busy_s"] * 1.001
    assert any(n.startswith("dynamic-slice_bitcast_fusion s8[") for n in names)
    assert 0.0 < reduced["mosaic_s"] < reduced["busy_s"]
    assert dict(map(tuple, reduced["modules"])).keys() == {"jit_admit_fn", "jit_decode_chunk_fn"}
    calls, mean_s = reduced["module_runs"]["jit_decode_chunk_fn"]
    assert calls == 1 and mean_s == pytest.approx(0.001382, rel=0.01)  # clipped at the cut
    assert reduced["whole_runs"] == {}  # so it is no round's time: both runs touch the cut's edges


def test_decode_round_readers_take_the_program_from_the_trace(reduced):
    from benchmark import counters, run as bench_run

    run = {"trace_reduced": reduced}
    # the recorded cut holds 1.382 ms of a decode round: a run the edge clipped, no round's time
    assert counters.decode_round_s(run) is None
    assert bench_run.load_reader("layer_metrics", "decode_round_ms").read(run) is None
    whole = {"trace_reduced": dict(reduced, whole_runs={counters.DECODE_PROGRAM: [3, 0.0512]})}
    assert counters.decode_round_s(whole) == 0.0512
    assert bench_run.load_reader("layer_metrics", "decode_round_ms").read(whole) == pytest.approx(51.2)
    assert bench_run.load_reader("layer_metrics", "decode_round_ms").read({"trace_reduced": None}) is None
    share = bench_run.load_reader("layer_metrics", "pallas_busy_share").read(run)
    assert share == pytest.approx(100.0 * reduced["mosaic_s"] / reduced["busy_s"])


def test_names():
    text = ('%branch_1_fun.5 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)} custom-call(s32[1]{0} %x), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.short_name(text) == "branch_1_fun.5" and tr.is_mosaic(text)
    assert tr.label(text) == "branch_1_fun bf16[32,8,4,128] [pallas]"
    assert tr.label("%while.38 = (s32[]{:T(128)}, s8[36]{0}) while(...)") == "while"
    assert tr.CONTAINERS.match(tr.short_name("%conditional.46 = (bf16[1]{0}) conditional(...)"))
    assert not tr.CONTAINERS.match(tr.short_name("%fusion.406 = f32[32]{0} fusion(...)"))
    assert tr.label("%custom-call.35 = bf16[36,52]{1,0} custom-call(...), custom_call_target=\"ConcatBitcast\"") \
        == "custom-call bf16[36,52]"


def test_interval_arithmetic():
    assert tr.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert tr.union_s([]) == 0.0
    assert tr.gaps([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps([(10, 20)], 0, 15) == [(0, 10)]
    assert tr.reduce_trace(FIXTURE, top=3)["device_ops"].__len__() == 3
