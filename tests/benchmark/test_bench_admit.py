"""The seven admission readers (PR 37) on a run of a tiny engine at the CPU and
on a hand-made trace: each gives a number where the program records what it
reads and None, without raising, on a run of a program that lacks it (the
parent's: no `admit` block, pairs for samples, no `engine.admit.dispatch`).
A file of its own: a PR may not edit a file the benchmark already has."""

import concurrent.futures as cf
import importlib.util
import os
import sys
import time
import types

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import admit_spans, run as bench_run, trace_reduce as tr  # noqa: E402
from llm_mcp_tpu.executor import GenerationEngine  # noqa: E402
from llm_mcp_tpu.telemetry import recorder as flight  # noqa: E402
from llm_mcp_tpu.telemetry.recorder import FlightRecorder  # noqa: E402

READERS = ("admit_program_share", "admit_rows_mean", "admit_pad_waste_pct", "event_gap_admit_share",
           "event_gap_admit_ms", "slot_vacant_ms", "slot_vacant_queued_ms")
OLD_TRACE = os.path.join(ROOT, "benchmark", "fixtures", "v5e_engine_phases_slice.xspace.txt")


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


# -- a run of a tiny engine: counters, samples, ring --------------------------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One long stream and a closed loop of short requests beside it, so that
    admissions are dispatched between the long stream's rounds and slots are
    taken again; the dict is what `benchmark/run.py:measure` hands a reader."""
    rec = FlightRecorder(capacity=8192, dump_dir=str(tmp_path_factory.mktemp("flight")))
    prev = flight.set_recorder(rec)
    gen = GenerationEngine("tiny-llm", max_slots=4, max_seq_len=192, dtype=jnp.float32,
                           decode_chunk=4).start()
    try:
        gen.generate("warm the shapes", max_tokens=6, temperature=0.0)
        start = {"perf": gen.perf_stats()}
        w0 = time.monotonic()

        def short_loop(k):
            return [gen.generate(f"short request {k} {i} " * (1 + i % 3), max_tokens=6, temperature=0.0)
                    for i in range(5)]

        with cf.ThreadPoolExecutor(3) as pool:
            long_one = pool.submit(gen.generate, "the long stream the others are admitted beside",
                                   max_tokens=100, temperature=0.0)
            loops = [pool.submit(short_loop, k) for k in range(2)]
            assert long_one.result()["usage"]["completion_tokens"] == 100
            assert all(len(f.result()) == 5 for f in loops)
        w1 = time.monotonic()
        end = {"perf": gen.perf_stats()}
        yield {"sut": {"gen": gen}, "start": start, "end": end, "window_abs": (w0, w1)}
    finally:
        gen.shutdown()
        flight.set_recorder(prev)


def old_program(run):
    """The same run as a program without this PR's records gives it: no
    `admit` block and no `samples_evicted` in `perf_stats()`, `samples(kind)`
    of pairs alone, no `admit_prog` in the ring, a trace without the
    dispatch's annotation (the recorded slice of PR 24's program)."""
    gen = run["sut"]["gen"]
    pairs = gen._perf.samples("event_gap")
    strip = lambda perf: {k: v for k, v in perf.items() if k not in ("admit", "samples_evicted")}  # noqa: E731
    old_gen = types.SimpleNamespace(
        _perf=types.SimpleNamespace(samples=lambda kind: list(pairs)),
        _flight=types.SimpleNamespace(snapshot=lambda etype=None: []),
        decode_chunk=gen.decode_chunk, max_slots=gen.max_slots)
    return {"sut": {"gen": old_gen}, "start": {"perf": strip(run["start"]["perf"])},
            "end": {"perf": strip(run["end"]["perf"])}, "window_abs": run["window_abs"],
            "trace_path": OLD_TRACE, "trace_reduced": tr.reduce_trace(OLD_TRACE)}


# -- a hand-made slice: runs of the admit program and the dispatches that caused them ---

T0 = 5000.0  # time.monotonic() of the trace's 0 ms, less its timestamp_ns
RUNS = [("jit_admit_fn(11)", 5, 13), ("jit_decode_chunk_fn(22)", 13, 63), ("jit_admit_fn(12)", 63, 77),
        ("jit_decode_chunk_fn(22)", 77, 127), ("jit_admit_fn(11)", 127, 135), ("jit_admit_fn(12)", 135, 149),
        ("jit_decode_chunk_fn(22)", 149, 199)]  # ms; the first admission was dispatched before the slice
DISPATCHES = [(8, 2.0), (9, 70.0), (10, 75.0), (11, 190.0)]  # (aid, ms): 0.3 ms each; 11 runs after the slice
SHAPES = {7: (1, 64), 8: (2, 64), 9: (1, 64), 10: (2, 64), 11: (1, 64)}
READS = [(7, 13.4, True), (8, 77.3, True), (9, 140.0, False), (10, 149.5, True)]  # (aid, ms, blocked)


def xspace(path):
    """The slice as a text proto `ProfileData.from_text_proto` reads: one op
    under every run, the engine thread's `engine.admit` phases with the
    dispatch's annotation (stat `aid`) nested in them."""
    ps = lambda ms: int(round(ms * 1e9))  # noqa: E731
    names = sorted({n for n, _a, _b in RUNS})
    mods = [f"    events {{ metadata_id: {names.index(n) + 1} offset_ps: {ps(a)} duration_ps: {ps(b - a)} }}"
            for n, a, b in RUNS]
    ops = [f"    events {{ metadata_id: 9 offset_ps: {ps(a)} duration_ps: {ps(b - a)} }}" for _n, a, b in RUNS]
    host = []
    for aid, at in DISPATCHES:
        host.append(f"    events {{ metadata_id: 1 offset_ps: {ps(at - 0.5)} duration_ps: {ps(1.5)} }}")
        host.append(f"    events {{ metadata_id: 2 offset_ps: {ps(at)} duration_ps: {ps(0.3)} "
                    f"stats {{ metadata_id: 1 int64_value: {aid} }} }}")
    text = "\n".join([
        'planes {', '  id: 1', '  name: "/device:TPU:0"',
        '  lines {', '    id: 1', '    name: "XLA Modules"', '    timestamp_ns: 1000000', *mods, '  }',
        '  lines {', '    id: 2', '    name: "XLA Ops"', '    timestamp_ns: 1000000', *ops, '  }',
        *[f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}' for i, n in enumerate(names)],
        '  event_metadata { key: 9 value { id: 9 name: "%fusion.1 = bf16[32,4096]{1,0} fusion(%p), kind=kLoop" } }',
        '}',
        'planes {', '  id: 2', '  name: "/host:CPU"',
        '  lines {', '    id: 1', '    name: "gen-engine"', '    timestamp_ns: 1000000', *host, '  }',
        '  event_metadata { key: 1 value { id: 1 name: "engine.admit" } }',
        '  event_metadata { key: 2 value { id: 2 name: "engine.admit.dispatch" } }',
        '  stat_metadata { key: 1 value { id: 1 name: "aid" } }',
        '}', ''])
    with open(path, "w") as f:
        f.write(text)
    return str(path)


def ring_of(reads=READS):
    """The flight ring beside that slice: a dispatch's event is stamped as its
    annotation closes, a read's as it returns."""
    at = dict(DISPATCHES)
    events = []
    for aid, (rows_padded, bucket) in SHAPES.items():
        t = T0 + (at[aid] + 0.35) / 1e3 if aid in at else T0 - 0.03
        events.append(("admit_prog", {"aid": aid, "kind": "batch", "rows": rows_padded, "rows_padded": rows_padded,
                                      "bucket": bucket, "t": t}))
    events += [("admit_read", {"aid": aid, "blocked": blocked, "t": T0 + ms / 1e3}) for aid, ms, blocked in reads]
    events.append(("admit", {"slot": 1, "prompt_tokens": 40}))  # the per-request event carries no aid

    def snapshot(etype=None):
        return [{"etype": e, "fields": f} for e, f in events if etype in (None, e)]

    return types.SimpleNamespace(snapshot=snapshot)


@pytest.fixture()
def traced(tmp_path):
    path = xspace(tmp_path / "admit_slice.xspace.txt")
    return {"trace_path": path, "trace_reduced": tr.reduce_trace(path),
            "sut": {"gen": types.SimpleNamespace(_flight=ring_of())}}


# -- each reader: a number where the program records it, None where it does not ------


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_a_number_from_a_run_that_holds_what_it_reads(name, served, traced):
    run = traced if name == "admit_program_share" else served
    value = reader(name).read(run)
    assert isinstance(value, float) and value == value and value >= 0
    if reader(name).UNIT == "%":
        assert value <= 100


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_none_and_does_not_raise_on_the_parents_program(name, served):
    assert reader(name).read(old_program(served)) is None
    assert reader(name).read({**old_program(served), "trace_path": None, "trace_reduced": None}) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_states_what_benchmark_json_will_say_of_it(name):
    mod = reader(name)
    assert mod.NAME == name and mod.BETTER in ("lower", "higher") and mod.__doc__
    assert mod.SOURCE in ("device_trace", "program_span", "program_counter")
    assert mod.LAYER in ("admission and scheduler", "step programs")
    assert mod.MOVES == ("itl_p95_ms" if name.startswith("event_gap") else "out_tokens_per_s")


def test_the_counter_readers_are_the_windows_difference_of_the_block(served):
    a, b = served["start"]["perf"]["admit"], served["end"]["perf"]["admit"]
    programs, prompts = b["programs"] - a["programs"], b["prompts"] - a["prompts"]
    assert prompts == 11 and 1 <= programs <= prompts  # the long stream and ten short requests
    assert reader("admit_rows_mean").read(served) == pytest.approx(prompts / programs)
    waste = 1 - (b["true_tokens"] - a["true_tokens"]) / (b["padded_tokens"] - a["padded_tokens"])
    assert reader("admit_pad_waste_pct").read(served) == pytest.approx(100 * waste) and 0 < waste < 1
    va, vb = a["vacancy"], b["vacancy"]
    count = vb["count"] - va["count"]
    assert count >= 5  # slots were taken again
    parts = [vb[k] - va[k] for k in ("cooling_s", "no_request_s", "queued_s")]
    assert reader("slot_vacant_ms").read(served) == pytest.approx(1e3 * sum(parts) / count)
    assert reader("slot_vacant_queued_ms").read(served) == pytest.approx(1e3 * parts[2] / count)
    assert reader("slot_vacant_queued_ms").read(served) <= reader("slot_vacant_ms").read(served)


def test_the_gap_readers_split_the_windows_samples_by_what_stood_between(served):
    got = admit_spans.gap_samples(served)
    w0, w1 = served["window_abs"]
    assert got and all(len(s) == 4 and w0 <= s[0] < w1 for s in got)  # every gap says what stood in it
    behind = [s[1] for s in got if s[2] >= 1]
    without = [s[1] for s in got if s[2] == 0]
    assert len(behind) >= 3 and len(without) >= 3  # the long stream rode both kinds of gap
    assert all(s[3] > 0 for s in got if s[2] >= 1) and all(s[3] == 0 for s in got if s[2] == 0)
    assert reader("event_gap_admit_share").read(served) == pytest.approx(100 * len(behind) / len(got))
    import statistics

    assert reader("event_gap_admit_ms").read(served) == pytest.approx(
        1e3 * (statistics.median(behind) - statistics.median(without)))
    # the readers that unpack pairs go on reading what they read
    from benchmark import spans

    assert spans.window_samples(served, "event_gap") == [s[1] for s in got]


@pytest.mark.parametrize("name", ["event_gap_admit_share", "event_gap_admit_ms"])
def test_a_window_that_lost_samples_reads_none(name, served, monkeypatch):
    perf = served["sut"]["gen"]._perf
    w0, _w1 = served["window_abs"]
    whole = perf.samples("event_gap", whole=True)
    monkeypatch.setattr(perf, "samples_evicted", {**perf.samples_evicted, "event_gap": 5})
    # pushed out while the window ran, but the oldest sample held is older than its start: nothing lost
    assert whole[0][0] < w0 and reader(name).read(served) is not None
    inside = [s for s in whole if s[0] >= w0]
    monkeypatch.setattr(perf, "samples", lambda kind, whole=False: list(inside))
    assert reader(name).read(served) is None  # the window's first samples are gone
    monkeypatch.setattr(perf, "samples_evicted", dict(served["start"]["perf"]["samples_evicted"]))
    assert reader(name).read(served) is not None  # nothing was pushed out: the engine had just started


# -- the trace's reader ----------------------------------------------------------------


def test_admit_program_share_is_the_admit_programs_seconds_over_the_slices_busy(traced, capsys):
    assert traced["trace_reduced"]["busy_s"] == pytest.approx(0.194)
    assert reader("admit_program_share").read(traced) == pytest.approx(100 * 0.044 / 0.194)
    line, = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("admit programs in the slice")]
    assert "4 runs, 4 dispatches annotated, 11.00 ms a run" in line
    assert line.endswith("1:64 -> 2 x 8.00, 2:64 -> 2 x 14.00")


def test_a_run_is_placed_by_a_read_that_waited_for_it_not_by_the_dispatch_before_it(traced):
    """The dispatch of aid 8 is annotated before the run of aid 7 begins
    (dispatched before the slice, still queued behind a round): pairing each
    annotation with the next run would shift every shape by one."""
    runs, disp = admit_spans.admit_runs(traced)
    assert [aid for aid, _a, _b in disp] == [8, 9, 10, 11] and disp[0][1] < runs[0][0]
    assert admit_spans.run_aids(traced, runs, disp, admit_spans.ring(traced, "admit_prog")) == {
        0: 7, 1: 8, 2: 9, 3: 10}
    assert admit_spans.runs_by_shape(traced, runs, disp) == {
        "1:64": [pytest.approx(8.0)] * 2, "2:64": [pytest.approx(14.0)] * 2}


@pytest.mark.parametrize("reads,why", [
    ([(7, 13.4, False), (8, 77.3, False)], "no read waited for its program"),
    ([(7, 13.4, True), (9, 77.3, True)], "two reads name two shifts"),
    ([(7, 40.0, True)], "no run ends beside the read"),
])
def test_runs_that_cannot_be_placed_keep_their_seconds_and_lose_their_shape(traced, reads, why):
    traced["sut"]["gen"]._flight = ring_of(reads)
    assert admit_spans.runs_by_shape(traced, *admit_spans.admit_runs(traced)) == {
        "?": [pytest.approx(v) for v in (8.0, 14.0, 8.0, 14.0)]}, why
    assert reader("admit_program_share").read(traced) == pytest.approx(100 * 0.044 / 0.194)


def test_the_share_is_read_only_where_runs_and_dispatches_agree(traced, tmp_path, monkeypatch):
    monkeypatch.setattr(sys.modules[__name__], "DISPATCHES", DISPATCHES[:1])  # four runs, one dispatch annotated
    path = xspace(tmp_path / "few.xspace.txt")
    run = {"trace_path": path, "trace_reduced": tr.reduce_trace(path), "sut": traced["sut"]}
    assert admit_spans.admit_runs(run) is None and reader("admit_program_share").read(run) is None


# -- since PR 38: rounds of two names, and a window whose admissions all ride ---------------

MIXED = [("jit_mixed_round_fn(33)", 199, 252), ("jit_mixed_round_fn(34)", 252, 312)]  # the two rungs, an executable each


def slice_of(tmp_path, monkeypatch, runs, dispatches):
    monkeypatch.setattr(sys.modules[__name__], "RUNS", runs)
    monkeypatch.setattr(sys.modules[__name__], "DISPATCHES", dispatches)
    path = xspace(tmp_path / "rounds.xspace.txt")
    return {"trace_path": path, "trace_reduced": tr.reduce_trace(path)}


def test_engine_host_ms_per_round_divides_by_the_rounds_of_both_names(traced, tmp_path, monkeypatch):
    """The slice's host seconds are four `engine.admit` phases of 1.5 ms; the
    host served a round that carried prompts as it served a plain one."""
    from benchmark import counters

    host = reader("engine_host_ms_per_round")
    assert counters.ROUND_PROGRAMS == (counters.DECODE_PROGRAM, "jit_mixed_round_fn")
    assert host.read(traced) == pytest.approx(6.0 / 3)  # three plain rounds and no other
    recorded = list(RUNS)
    both = slice_of(tmp_path, monkeypatch, recorded + MIXED, DISPATCHES)
    assert host.read(both) == pytest.approx(6.0 / 5)  # not 6.0 / 3: what the reader gave until PR 40
    assert reader("decode_round_ms").read(both) == pytest.approx(50.0)  # a PLAIN round's time, as before
    mixed_only = slice_of(tmp_path, monkeypatch, [r for r in recorded if "decode" not in r[0]] + MIXED, DISPATCHES)
    assert host.read(mixed_only) == pytest.approx(6.0 / 2) and reader("decode_round_ms").read(mixed_only) is None


@pytest.fixture()
def riding(tmp_path, monkeypatch):
    """A window in which every admission rode a decode round (decode_closed
    since PR 38): the `admit` block's programs stand still while its rides and
    vacancies grow, no gap holds an admit program, the slice holds rounds of
    both names and no run of `jit_admit_fn`."""
    def block(n):
        return {"programs": 3, "prompts": 5, "true_tokens": 200, "padded_tokens": 320,
                "rides": {"rounds": n, "prompts": 2 * n},
                "vacancy": {"count": n, "cooling_s": 0.050 * n, "no_request_s": 0.0, "queued_s": 0.002 * n}}

    gaps = [(100.0 + k, 0.052, 0, 0) for k in range(40)]
    perf = types.SimpleNamespace(samples=lambda kind, whole=False: list(gaps) if whole else [g[:2] for g in gaps],
                                 samples_evicted={"event_gap": 0, "stream_lag": 0})
    edge = lambda n: {"perf": {"admit": block(n), "samples_evicted": dict(perf.samples_evicted)}}  # noqa: E731
    run = slice_of(tmp_path, monkeypatch, [r for r in RUNS if "admit" not in r[0]] + MIXED, [])
    return {**run, "sut": {"gen": types.SimpleNamespace(_perf=perf, _flight=ring_of([]))},
            "start": edge(0), "end": edge(40), "window_abs": (100.0, 140.0)}


@pytest.mark.parametrize("name", ["admit_program_share", "admit_rows_mean", "admit_pad_waste_pct",
                                  "event_gap_admit_ms"])
def test_a_reader_of_the_admit_program_gives_none_where_none_ran(name, riding):
    assert riding["trace_reduced"]["module_runs"].get(admit_spans.ADMIT_PROGRAM) is None
    assert reader(name).read(riding) is None


def test_the_readers_of_gaps_and_vacancies_still_read_where_every_admission_rides(riding):
    assert reader("event_gap_admit_share").read(riding) == 0.0  # a reading: what the ride bought
    assert reader("slot_vacant_ms").read(riding) == pytest.approx(52.0)
    assert reader("slot_vacant_queued_ms").read(riding) == pytest.approx(2.0)


# -- the builder's script ----------------------------------------------------------------


def test_the_script_reads_the_seven_and_prints_what_no_metric_reads(served, capsys):
    spec = importlib.util.spec_from_file_location("admit_metrics", os.path.join(ROOT, "scripts", "admit_metrics.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert script.READERS == READERS
    assert script.diff({"a": 1, "b": {"c": 2.0}}, {"a": 4, "b": {"c": 2.5, "d": 1}}) == {"a": 3, "b": {"c": 0.5, "d": 1}}
    assert script.extras(served) is None
    import json

    line, = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("admission: ")]
    doc = json.loads(line[len("admission: "):])
    assert doc["window"]["prompts"] == 11 and sum(doc["window"]["by_shape"].values()) == doc["window"]["programs"]
    assert doc["samples_evicted"]["at_read"] == {"event_gap": 0, "stream_lag": 0}
    assert doc["event_gap_samples"]["in_window"] == len(admit_spans.gap_samples(served))
