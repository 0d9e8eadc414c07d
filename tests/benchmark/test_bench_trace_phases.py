"""The trace reduction and the trace-reading layer metrics on a second
recorded slice: 14 ms cut from a v5e trace of the decode_closed cell taken
with PR 24's program (`scripts/cut_xplane.py ... --at-ms 470 --ms 14
--host-prefix engine. --host-prefix D2H --host-prefix ReadSyncFlag`). It holds
the last 4.9 ms of a `jit_admit_fn`, 3.47 ms with nothing on the device while
the engine thread is still in `engine.admit` (its blocking read of the first
tokens has just returned), and the first 5.6 ms of a `jit_decode_chunk_fn`
with five layers' `decode_attn_q8_blocked` kernels; the engine thread's line
is named `gen-engine`. Operations across the cut were clipped to it. (A file of
its own beside test_bench_trace.py: a PR may not edit a file the benchmark
already has.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run, spans, trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "v5e_engine_phases_slice.xspace.txt")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(FIXTURE)


@pytest.fixture()
def run():
    return {"trace_path": FIXTURE}


def test_the_gap_is_named_after_the_engines_phase(reduced):
    (name, seconds), = [g for g in reduced["idle_gaps"] if g[1] > 1e-3]
    assert name == "jit_admit_fn -> jit_decode_chunk_fn [gen-engine: engine.admit]"
    assert seconds == pytest.approx(0.0034663, rel=0.01)
    assert reduced["busy_s"] == pytest.approx(0.010530, rel=0.01)
    assert reduced["window_s"] == pytest.approx(0.014, rel=0.001)
    assert reduced["module_runs"]["jit_decode_chunk_fn"][0] == 1


def test_the_kernel_is_listed_by_its_own_name(reduced):
    kernels = [(n, s) for n, s in reduced["device_ops"] if n.endswith("[pallas]")]
    assert ("decode_attn_q8_blocked bf16[32,8,4,128] [pallas]", pytest.approx(0.000483844, rel=0.01)) in kernels
    assert not any(n.startswith("branch_") for n, _s in reduced["device_ops"])
    assert 0 < reduced["mosaic_s"] < reduced["busy_s"]


def test_decode_attn_ms_finds_its_kernel_inside_the_decode_program(run):
    chips, _host = spans.planes(run)
    total, rounds, found = spans.kernel_seconds(chips, "jit_decode_chunk_fn", "decode_attn", whole=False)
    assert (rounds, found) == (1, {"decode_attn_q8_blocked"})
    assert total == pytest.approx(0.000483844, rel=0.01)  # five layers of the 144 a round has
    # ... which is why a run the cut's edge clipped is no round: until PR 47 this read 0.4838 ms
    # "a round", and on the chip a slice with two plain rounds, one of them cut, 106% of a roofline
    assert spans.kernel_seconds(chips, "jit_decode_chunk_fn", "decode_attn")[1] == 0
    assert bench_run.load_reader("layer_metrics", "decode_attn_ms").read(run) is None
    # the first recorded slice is the parent's program: its kernel is `branch_1_fun`
    old = {"trace_path": os.path.join(ROOT, "benchmark", "fixtures", "v5e_decode_slice.xspace.txt")}
    assert bench_run.load_reader("layer_metrics", "decode_attn_ms").read(old) is None
    assert bench_run.load_reader("layer_metrics", "engine_host_ms_per_round").read(old) is None


def test_engine_host_ms_takes_the_phases_less_their_blocking_reads(run):
    _chips, host = spans.planes(run)
    assert "gen-engine" in host and run["_planes"] is spans.planes(run)  # read once
    names = {"engine.admit", "engine.admit.sync", "engine.dispatch", "engine.emit", "engine.prefill",
             "engine.fetch", "engine.fetch.sync"}
    s = spans.host_seconds(host, names)
    assert s["engine.admit"] == pytest.approx(0.008567, rel=0.01)  # clipped at the cut's start
    assert s["engine.admit.sync"] == pytest.approx(0.007493, rel=0.01)
    assert s["engine.fetch.sync"] < s["engine.fetch"] < 0.001
    want = (s["engine.admit"] - s["engine.admit.sync"] + s["engine.dispatch"] + s["engine.emit"]
            + s["engine.prefill"])
    got = bench_run.load_reader("layer_metrics", "engine_host_ms_per_round").read(run)
    assert got == pytest.approx(1e3 * want) and 3.0 < got < 4.5  # one round in the cut
