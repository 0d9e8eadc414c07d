"""The three readers PR 54 adds (`mixed_round_share` and `round_stall_share`
from the engine's account of rounds, `benchmark/round_account.py`;
`mixed_round_ms` from the trace's whole runs of the mixed step program, with
the account's ms by rung logged beside it) on a hand-made run whose two
snapshots a real `RoundAccount` wrote, against a hand count: a number where
the program keeps the account, `round_stall_share` 0.0 where the window booked
no stall, `mixed_round_ms` None where the slice holds no whole mixed round, and
None without raising from a program that lacks the block (the parent's). A
file of its own: a PR may not edit a file the benchmark has."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import round_account, run as bench_run  # noqa: E402
from llm_mcp_tpu.telemetry.perf import RoundAccount  # noqa: E402

READERS = ("mixed_round_ms", "mixed_round_share", "round_stall_share")
FIVE = ["decode_closed", "solar_decode_closed", "olmo_hybrid_decode_closed", "granite_decode_closed",
        "lfm2_decode_closed"]
PHASES = dict.fromkeys(("dispatch", "fetch", "admit", "prefill", "emit", "idle"), 0.0)
W0, W1 = 1000.0, 1040.0  # the window, on time.monotonic()
# what `trace_reduce.reduce` leaves of an 8 s slice: [whole runs, mean seconds a run] by step program
TRACE = {"whole_runs": {"jit_decode_chunk_fn": [70, 0.05146], "jit_mixed_round_fn": [81, 0.05391]}}


def reader(name):
    return bench_run.load_reader("layer_metrics", name)


def rounds_of(acct, prog, n, told, ms, rows=32):
    for i in range(n):
        acct.fetched(prog, rows, rows * 4)
        if i < told:
            acct.told(prog, ms / 1e3, rows, rows * 4)
        acct.delivered(prog, rows * 4 - 3)


def run_of(acct, window, stall=None):
    """Snapshots before and after `window(acct)`; the account's rows before it
    are the set-up's, which the readers must subtract."""
    rounds_of(acct, "plain", 50, 40, 52.0)
    rounds_of(acct, "mixed_128", 7, 5, 90.0)  # the warm-up's rides, slower: not the window's
    acct.retired("plain", 1, 1.0, 0.0, dict(PHASES), 3)
    acct.retired("plain", 2, 8.0, 0.0, {**PHASES, "dispatch": 6.9}, 4)  # a first dispatch of the set-up
    start = {"t": W0, "perf": {"rounds": acct.stats()}}
    window(acct)
    end = {"t": W1, "perf": {"rounds": acct.stats()}}
    if stall is not None:  # the rows' `t` is the account's own time.monotonic(): place the window's rows in it
        for row in end["perf"]["rounds"]["stalls"]["recent"][-stall:]:
            row["t"] = W0 + 12.5
    return {"start": start, "end": end, "window_abs": (W0, W1), "trace_reduced": dict(TRACE)}


def the_window(acct):
    rounds_of(acct, "plain", 300, 200, 51.4)
    rounds_of(acct, "mixed_128", 340, 300, 53.5, rows=31)
    rounds_of(acct, "mixed_256", 60, 50, 56.0, rows=30)


def with_a_stall(acct):
    the_window(acct)
    acct.unchain()
    acct.retired("mixed_128", 400, 20.0, 0.0, {**PHASES, "dispatch": 7.0}, 4)
    acct.retired("mixed_128", 401, 22.65, 2.6, {**PHASES, "dispatch": 7.0, "admit": 2.61, "emit": 0.03}, 4)


@pytest.fixture()
def run():
    return run_of(RoundAccount(), the_window)


def old_program(run):
    strip = lambda edge: {**edge, "perf": {k: v for k, v in edge["perf"].items() if k != "rounds"}}  # noqa: E731
    return {**run, "start": strip(run["start"]), "end": strip(run["end"])}


def test_the_readers_are_the_windows_difference_of_the_account(run, capsys):
    rows = round_account.by_program(run)
    assert {p: (r["rounds"], r["told"]) for p, r in rows.items()} == {
        "plain": (300, 200), "mixed_128": (340, 300), "mixed_256": (60, 50)}
    # the metric is the trace's, the twin of decode_round_ms; the account's ms by rung is logged beside it
    assert reader("mixed_round_ms").read(run) == pytest.approx(53.91)
    assert reader("decode_round_ms").read(run) == pytest.approx(51.46)
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("rounds of the window by program (host clock): ")]
    assert line.endswith("mixed_128 -> 340 rounds, 300 told, 53.500 ms, mixed_256 -> 60 rounds, 50 told, 56.000 ms, "
                         "plain -> 300 rounds, 200 told, 51.400 ms")
    assert reader("mixed_round_share").read(run) == pytest.approx(100 * 400 / 700)
    assert round_account.ms(rows["plain"]) == pytest.approx(51.4)
    # what a later benchmark issue may re-point at the account: yield and occupancy of EVERY round
    assert rows["mixed_128"]["delivered"] == 340 * (31 * 4 - 3) and rows["mixed_128"]["row_steps"] == 340 * 31 * 4


def test_no_stall_in_the_window_is_a_reading_of_zero(run, capsys):
    assert run["end"]["perf"]["rounds"]["stalls"]["count"] == 1  # the set-up's first dispatch: not the window's
    assert reader("round_stall_share").read(run) == 0.0
    line, = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("stalls of the window: ")]
    assert line.startswith("stalls of the window: 0 of 0.000 s, excess 0.000 s, longest 0.000 s, by_phase {}")


def test_a_stall_is_its_excess_over_the_windows_seconds_and_is_logged_by_phase(capsys):
    run = run_of(RoundAccount(), with_a_stall, stall=1)
    mean = (5 * 90.0 + 300 * 53.5) / 305 / 1e3  # the rung's mean told round when the stall was booked, since boot
    assert reader("round_stall_share").read(run) == pytest.approx(100 * (2.65 - mean) / 40.0)
    st = round_account.stalls(run)
    assert (st["count"], st["longest_s"], st["window_s"]) == (1, pytest.approx(2.65), 40.0)
    assert st["by_phase"] == {"admit": [1, pytest.approx(2.65 - mean, abs=1e-6)]}
    assert [(r["phase"], r["program"], r["rid"], r["wait_s"]) for r in st["recent"]] == [("admit", "mixed_128", 401, 2.6)]
    line, = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("stalls of the window: ")]
    assert "1 of 2.650 s" in line and "longest 2.650 s" in line and "'admit': [1, " in line
    # the rounds' readers are the same numbers beside it
    assert reader("mixed_round_share").read(run) == pytest.approx(100 * 400 / 700)


def test_mixed_round_ms_reads_none_where_the_slice_holds_no_whole_mixed_round(capsys):
    def untold(acct):
        rounds_of(acct, "plain", 80, 60, 51.0)
        rounds_of(acct, "mixed_128", 20, 0, 54.0)  # an admit program of its own between every two

    # the account could tell no mixed round and the trace timed them all the same
    run = run_of(RoundAccount(), untold)
    assert reader("mixed_round_ms").read(run) == pytest.approx(53.91)
    assert "mixed_128 -> 20 rounds, 0 told, no ms" in capsys.readouterr().out
    assert reader("mixed_round_share").read(run) == pytest.approx(20.0)

    def no_rides(acct):
        rounds_of(acct, "plain", 80, 60, 51.0)

    run = run_of(RoundAccount(), no_rides)
    run["trace_reduced"] = {"whole_runs": {"jit_decode_chunk_fn": [150, 0.051]}}
    assert reader("mixed_round_ms").read(run) is None and reader("mixed_round_share").read(run) == 0.0
    assert reader("mixed_round_ms").read({**run, "trace_reduced": None}) is None  # an untraced run
    assert reader("mixed_round_share").read(run_of(RoundAccount(), lambda acct: None)) is None  # a window without rounds


def test_a_program_first_dispatched_inside_the_window_starts_at_zero():
    def window(acct):
        rounds_of(acct, "fused", 4, 2, 70.0)

    rows = round_account.by_program(run_of(RoundAccount(), window))
    assert (rows["fused"]["rounds"], rows["fused"]["told"]) == (4, 2) and rows["plain"]["rounds"] == 0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_none_and_does_not_raise_on_the_parents_program(name, run):
    # the trace names the mixed step program on the parent too: its mean run is read there, and nothing logged
    want = pytest.approx(53.91) if name == "mixed_round_ms" else None
    assert reader(name).read(old_program(run)) == want
    no_engine = {**run, "start": {"t": W0}, "end": {"t": W1}, "trace_reduced": {"whole_runs": {"jit_fwd": [800, 0.0495]}}}
    assert reader(name).read(no_engine) is None  # the embedding cell


@pytest.mark.parametrize("name", READERS)
def test_the_entry_is_found_by_name_and_says_what_its_reader_says(name, bench):
    mod = reader(name)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert mod.NAME == name and mod.__doc__
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
    assert entry["source"] == ("device_trace" if name == "mixed_round_ms" else "program_counter")
    assert entry["better"] == "lower"
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    if name == "round_stall_share":  # every generation cell; the rings of K-EXAONE's rule the ride out
        assert entry["workloads"] == FIVE[:4] + ["kexaone_reason_closed"] + FIVE[4:]
    else:
        assert entry["workloads"] == FIVE
    assert entry["moves"] == ("itl_p95_ms" if name == "mixed_round_ms" else "out_tokens_per_s")


def test_the_script_prints_the_windows_rounds_and_nothing_for_a_run_without_the_account(run):
    spec = importlib.util.spec_from_file_location("admit_metrics", os.path.join(ROOT, "scripts", "admit_metrics.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    got = script.rounds(run)
    json.dumps(got)  # it goes into the `admission:` line
    assert {p: (r["rounds"], r["told"], r["ms"]) for p, r in got["by_program"].items()} == {
        "plain": (300, 200, pytest.approx(51.4)), "mixed_128": (340, 300, pytest.approx(53.5)),
        "mixed_256": (60, 50, pytest.approx(56.0))}
    assert got["stalls"]["count"] == 0 and got["stalls"]["by_phase"] == {}
    assert script.rounds(old_program(run)) is None
    assert not hasattr(script, "stalls") and not hasattr(script, "rounds_by_program")
