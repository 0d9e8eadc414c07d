"""The engine's account of rounds (PR 54: `telemetry/perf.py:RoundAccount`,
`perf_stats()["rounds"]`): every round by step program against a hand count
from the flight ring, the device seconds told where a round ENDS (a mixed
round at its ride's read, a plain one at its fetch), the stalls of the
in-flight queue named by the loop's phase. On the CPU at a tiny size: counts,
and clocks the test itself holds."""

import gc
import time

import jax.numpy as jnp
import numpy as np
import pytest

from llm_mcp_tpu.executor import GenerationEngine
from llm_mcp_tpu.executor.engine import _DispatchedAdmit, _DispatchedRound
from llm_mcp_tpu.telemetry import perf
from llm_mcp_tpu.telemetry import recorder as flight
from llm_mcp_tpu.telemetry.perf import STALL_ROWS, STALL_S, RoundAccount
from llm_mcp_tpu.telemetry.recorder import CompileLedger, FlightRecorder
from test_mixed_round import _drain, _engine, _submit, _wait_active

K = 2  # decode_chunk
PHASES = dict.fromkeys(("dispatch", "fetch", "admit", "prefill", "emit", "idle"), 0.0)


# -- the account alone ----------------------------------------------------------------


def test_rows_by_program_and_their_totals():
    acct = RoundAccount()
    acct.fetched("plain", 32, 128)
    acct.fetched("mixed_128", 31, 124)
    acct.fetched("mixed_128", 30, 120)
    acct.told("mixed_128", 0.0539, 31, 124)
    acct.told("plain", 0.0514, 32, 128)
    acct.delivered("mixed_128", 119)
    by = acct.stats()["by_program"]
    assert set(by) == {"plain", "mixed_128"} and set(by["plain"]) == set(RoundAccount.ROW)
    assert by["mixed_128"] == {
        "rounds": 2, "rows": 61, "row_steps": 244, "delivered": 119, "told": 1, "told_rows": 31,
        "told_tokens": 124, "device_s": pytest.approx(0.0539)}
    assert by["plain"]["rounds"] == 1 and by["plain"]["delivered"] == 0
    assert acct.totals() == {"told": 2, "told_rows": 63, "told_tokens": 252, "device_s": pytest.approx(0.1053)}


def retire(acct, t, prog="plain", rid=1, wait=0.0, firsts=0, **phases):
    return acct.retired(prog, rid, t, wait, {**PHASES, **phases}, firsts)


def test_a_stall_is_an_interval_over_the_threshold_named_by_the_phase_that_held_it():
    acct = RoundAccount()
    assert retire(acct, 10.0) is None  # the first retirement closes no interval
    assert retire(acct, 10.0 + STALL_S, fetch=STALL_S) is None  # at the threshold: no stall
    t0 = time.monotonic()
    row = retire(acct, 11.0, rid=7, wait=0.01, fetch=0.21, emit=0.75, dispatch=0.003)
    assert row == {"t": pytest.approx(t0, abs=1.0), "seconds": pytest.approx(0.8), "excess_s": pytest.approx(0.8),
                   "phase": "emit", "program": "plain", "rid": 7, "gc_s": 0.0, "wait_s": 0.01}
    st = acct.stats()["stalls"]
    assert (st["count"], st["by_phase"]) == (1, {"emit": [1, pytest.approx(0.8)]})
    assert st["seconds"] == st["longest_s"] == pytest.approx(0.8) and st["recent"] == [row]
    # the phases are sums since boot: the next interval is named from ITS seconds alone
    assert retire(acct, 14.0, fetch=0.21 + 2.9, emit=0.76)["phase"] == "fetch"
    st = acct.stats()["stalls"]
    assert st["by_phase"] == {"emit": [1, pytest.approx(0.8)], "fetch": [1, pytest.approx(3.0)]}
    assert st["longest_s"] == pytest.approx(3.0) and st["seconds"] == pytest.approx(3.8)


def test_a_first_dispatch_inside_the_interval_names_the_stall():
    acct = RoundAccount()
    retire(acct, 1.0, firsts=4)
    assert retire(acct, 8.0, firsts=5, dispatch=6.9, fetch=0.05)["phase"] == "first_dispatch"
    assert retire(acct, 9.0, firsts=5, dispatch=7.8, fetch=0.06)["phase"] == "dispatch"


def test_the_threshold_is_twice_the_programs_mean_told_round_and_the_excess_is_over_that_mean():
    acct = RoundAccount()
    for _ in range(4):
        acct.told("mixed_256", 0.122, 64, 256)
    retire(acct, 1.0)
    assert retire(acct, 1.24, prog="mixed_256") is None  # over 0.2 s, under two rounds of 122 ms
    row = retire(acct, 1.54, prog="mixed_256", fetch=0.3)
    assert (row["seconds"], row["excess_s"]) == (pytest.approx(0.3), pytest.approx(0.3 - 0.122))
    # a program no round of which has told: the constant alone, the whole interval its excess
    row = retire(acct, 1.78, prog="admit", admit=0.24)
    assert (row["seconds"], row["excess_s"], row["phase"]) == (pytest.approx(0.24), pytest.approx(0.24), "admit")
    assert acct.stats()["stalls"]["excess_s"] == pytest.approx(0.3 - 0.122 + 0.24)


def test_an_idle_loop_breaks_the_chain_and_the_newest_stalls_stay_whole():
    acct = RoundAccount()
    retire(acct, 1.0)
    acct.unchain()  # nothing in flight, nothing to dispatch: 8 s without a request is no stall
    assert retire(acct, 9.0) is None and acct.stats()["stalls"]["count"] == 0
    for i in range(STALL_ROWS + 4):
        assert retire(acct, 10.0 + i, rid=i, emit=float(i)) is not None
    st = acct.stats()["stalls"]
    assert st["count"] == STALL_ROWS + 4 and [r["rid"] for r in st["recent"]] == list(range(4, STALL_ROWS + 4))


def test_the_collectors_seconds_are_booked_an_interval_and_a_stall(monkeypatch):
    acct = RoundAccount()
    assert perf._on_gc in gc.callbacks and gc.callbacks.count(perf._on_gc) == 1
    before = perf.gc_seconds()
    gc.collect()
    assert perf.gc_seconds() > before and perf._gc["v"][0] == 0.0  # the pair of stamps is live, none in progress
    monkeypatch.setitem(perf._gc, "v", (0.0, 1.0))
    retire(acct, 1.0)
    monkeypatch.setitem(perf._gc, "v", (0.0, 1.3))
    assert retire(acct, 1.5, emit=0.5)["gc_s"] == pytest.approx(0.3)
    monkeypatch.setitem(perf._gc, "v", (0.0, 1.31))
    assert retire(acct, 1.55) is None  # no stall: the account's sum alone
    st = acct.stats()
    assert st["gc_s"] == pytest.approx(0.31) and st["stalls"]["gc_s"] == pytest.approx(0.3)
    # a collection still in progress when the loop comes by (it let go of the GIL in a destructor)
    # gives the interval the part of it so far, and the next interval the rest
    monkeypatch.setattr(perf.time, "perf_counter", lambda: 100.25)
    monkeypatch.setitem(perf._gc, "v", (100.0, 1.31))
    assert retire(acct, 1.9, fetch=0.35)["gc_s"] == pytest.approx(0.25)
    monkeypatch.setattr(perf.time, "perf_counter", lambda: 100.33)
    perf._on_gc("stop", {})
    assert perf._gc["v"] == (0.0, pytest.approx(1.64))
    assert retire(acct, 1.95) is None and acct.stats()["gc_s"] == pytest.approx(0.31 + 0.33)


# -- where a round ends: fakes through the engine's own two reads ---------------------


class Late:
    """A device array whose read blocks: not ready when asked, `wait` s to come."""

    def __init__(self, a, wait):
        self.a, self.wait = np.asarray(a), wait

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.wait)
        return self.a


@pytest.fixture(scope="module")
def bare(tmp_path_factory):
    """An engine that is built and never started: the test's thread is the
    loop's, and hands `_read_admit` and `_complete_round` what it likes."""
    rec = FlightRecorder(capacity=1024, dump_dir=str(tmp_path_factory.mktemp("flight")))
    prev = flight.set_recorder(rec), flight.set_compile_ledger(CompileLedger())
    gen = GenerationEngine("tiny-llm", max_slots=4, max_seq_len=64, dtype=jnp.float32, decode_chunk=K)
    yield gen, rec
    gen.shutdown()  # its watchdog's thread ends, so the engine and its executables can go
    flight.set_recorder(prev[0])
    flight.set_compile_ledger(prev[1])


def fake_round(rid, dx, out, rows=3, **kw):
    now = time.perf_counter()
    return _DispatchedRound(out=out, entries=[(i, object(), i) for i in range(rows)], base=np.zeros(4, np.int32),
                            t0=now, t_disp=now, rid=rid, dx=dx, **kw)


def fake_admit(toks0, **kw):
    now = time.perf_counter()
    return _DispatchedAdmit(toks0=toks0, entries=[], t0=now, t_call=now, first=True, **kw)


def ready():
    return jnp.zeros((K, 4), jnp.int32).block_until_ready()


def timed(gen, key, fn, item):
    """As the loop's `timed` books a call's seconds under its phase."""
    t0 = time.perf_counter()
    try:
        return fn(item)
    finally:
        gen._phase_s[key] += time.perf_counter() - t0


def test_a_round_ends_at_the_first_read_that_waited_for_its_program(bare):
    gen, rec = bare
    acct = gen._perf.rounds
    row = lambda prog: acct.stats()["by_program"].get(prog, dict.fromkeys(RoundAccount.ROW, 0))  # noqa: E731
    zeros = np.zeros((K, 4), np.int32)
    # four rounds dispatched back to back before the first is fetched: a plain one,
    # a mixed one whose ride is queued before it, a plain one, and a plain one with
    # an admit program of its own before it (its dx is two on)
    a = fake_round(101, 51, Late(zeros, 0.01))
    m = fake_round(102, 52, ready(), prog="mixed_128", phase="fused")
    ride = fake_admit(Late(np.zeros(2, np.int32), 0.03), rid=m.rid, round=m)
    p = fake_round(103, 53, Late(zeros, 0.02))
    own = fake_admit(Late(np.zeros(1, np.int32), 0.01), aid=9)
    q = fake_round(104, 55, Late(zeros, 0.01))

    timed(gen, "fetch", gen._complete_round, a)  # whatever it told, its fetch is where `m` begins
    assert gen._prev_end[:2] == (101, 51) and gen._prev_end[3] is True
    t_a, mixed0, plain0 = gen._prev_end[2], row("mixed_128"), row("plain")

    # the mixed round is told at its ride's blocked read, under its rung's key
    timed(gen, "admit", gen._read_admit, ride)
    t_m = gen._prev_end[2]
    assert gen._prev_end == (102, 52, t_m, True) and m.ended and t_m - t_a >= 0.03
    got = row("mixed_128")
    assert got["told"] - mixed0["told"] == 1 and got["told_rows"] - mixed0["told_rows"] == 3
    assert got["told_tokens"] - mixed0["told_tokens"] == 3 * K
    assert got["device_s"] - mixed0["device_s"] == pytest.approx(t_m - t_a, abs=1e-9)
    assert got["rounds"] == mixed0["rounds"]  # booked as a round at its fetch
    # ... and its own fetch, which finds it ended, tells nothing twice and moves no end
    timed(gen, "fetch", gen._complete_round, m)
    assert gen._prev_end == (102, 52, t_m, True)
    got = row("mixed_128")
    assert got["told"] - mixed0["told"] == 1 and got["rounds"] - mixed0["rounds"] == 1
    assert (got["rows"] - mixed0["rows"], got["row_steps"] - mixed0["row_steps"]) == (3, 3 * K)

    # the plain round behind it is told at its fetch, from the ride's read on
    timed(gen, "fetch", gen._complete_round, p)
    t_p = gen._prev_end[2]
    got = row("plain")
    assert got["told"] - plain0["told"] == 1
    assert got["device_s"] - plain0["device_s"] == pytest.approx(t_p - t_m, abs=1e-9) and t_p - t_m >= 0.02

    # an admit program of its own between: its read is a retirement and no round's
    # end, and the round behind it adds to `rounds` and tells nothing
    timed(gen, "admit", gen._read_admit, own)
    assert gen._prev_end == (103, 53, t_p, True)
    plain1 = row("plain")
    timed(gen, "fetch", gen._complete_round, q)
    got = row("plain")
    assert (got["told"], got["device_s"]) == (plain1["told"], plain1["device_s"])
    assert got["rounds"] - plain1["rounds"] == 1
    assert acct.stats()["stalls"]["count"] == 0 and rec.snapshot(etype="stall") == []

    # a first dispatch inside an interval names the stall it makes, and the round
    # that closes a stall tells nothing: the seconds are the stall's
    told = acct.totals()
    r = fake_round(105, 56, Late(zeros, STALL_S + 0.05))
    assert gen._note_exec_shape("a shape never seen", 1)
    gen._compile_obs("cow", ("t",), 0.0)
    timed(gen, "fetch", gen._complete_round, r)
    s = fake_round(106, 57, Late(zeros, STALL_S + 0.05))
    timed(gen, "fetch", gen._complete_round, s)
    st = acct.stats()["stalls"]
    assert st["count"] == 2 and set(st["by_phase"]) == {"first_dispatch", "fetch"}
    assert [(x["phase"], x["program"], x["rid"]) for x in st["recent"]] == [
        ("first_dispatch", "plain", 105), ("fetch", "plain", 106)]
    assert all(STALL_S + 0.05 <= x["wait_s"] <= x["seconds"] < STALL_S + 0.5 for x in st["recent"])
    assert [e["fields"] for e in rec.snapshot(etype="stall")] == st["recent"]
    assert acct.totals() == told


# -- a served batch with rides against the ring's count -------------------------------


def test_the_account_is_the_rings_count_of_a_served_batch_with_rides(monkeypatch, tmp_path):
    rec = FlightRecorder(capacity=8192, dump_dir=str(tmp_path))
    prev = flight.set_recorder(rec)
    eng = _engine(monkeypatch, own=True, max_slots=4, decode_chunk=K).start()
    try:
        eng.generate("warm the shapes", max_tokens=6, temperature=0.0)
        long = [_submit(eng, f"row {i} keeps decoding for a while", max_tokens=120) for i in range(3)]
        _wait_active(eng, 3)
        for i in range(3):  # one after the other beside three decoding rows: each rides a round
            _drain(_submit(eng, f"rider {i} rides along " * (i + 1), max_tokens=6))
        for r in long:
            _drain(r)
    finally:
        eng.shutdown()
        flight.set_recorder(prev)
    ring = lambda etype: [e["fields"] for e in rec.snapshot(etype=etype)]  # noqa: E731
    got = eng.perf_stats()["rounds"]["by_program"]
    rides = eng.perf_stats()["admit"]["rides"]
    # the hand count: the ring's one event a round at its dispatch, fetch and emit
    prog = {f["rid"]: "plain" for f in ring("decode")}
    mixed = {f["rid"]: f for f in ring("mixed")}
    prog.update({rid: f"mixed_{f['padded_tokens']}" for rid, f in mixed.items()})
    rows = {f["rid"]: f["rows"] for f in ring("decode") + ring("mixed")}
    fetched = [f["rid"] for f in ring("fetch")]
    assert sum(r["rounds"] for r in got.values()) == len(fetched) == len(prog)
    want = {p: dict.fromkeys(("rounds", "rows", "row_steps", "delivered"), 0) for p in set(prog.values())}
    for rid in fetched:
        w = want[prog[rid]]
        w["rounds"] += 1
        w["rows"] += rows[rid]
        w["row_steps"] += rows[rid] * K
    for f in ring("emit"):
        want[prog[f["rid"]]]["delivered"] += f["delivered"]
    assert {p: {k: r[k] for k in want[p]} for p, r in got.items()} == want
    riding = [r for p, r in got.items() if p.startswith("mixed_")]
    assert riding and set(got) - {"plain"} <= {"mixed_128", "mixed_256"}
    # the three riders rode, and whichever long row was queued behind a decoding one
    # (the prompts they carried are the admission account's book, not doubled here)
    assert sum(r["rounds"] for r in riding) == rides["rounds"] >= 3 and rides["prompts"] >= 3
    # what is told is a part of the rounds
    for r in got.values():
        assert 0 <= r["told"] <= r["rounds"] and r["told_tokens"] == r["told_rows"] * K
        assert r["delivered"] <= r["row_steps"]


# -- the loop: a stall by its phase, the roofline's book, the host plane ---------------


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    rec = FlightRecorder(capacity=4096, dump_dir=str(tmp_path_factory.mktemp("flight")))
    prev = flight.set_recorder(rec)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_SPEC", "0")  # every round a pipelined one: no draft, no synchronous verify round
        gen = GenerationEngine("tiny-llm", max_slots=4, max_seq_len=192, dtype=jnp.float32, decode_chunk=4).start()
    gen.generate("every shape the tests below meet", max_tokens=40, temperature=0.0)
    yield gen, rec
    gen.shutdown()
    flight.set_recorder(prev)


def test_a_sleep_in_the_emit_books_one_stall_named_emit(plain, monkeypatch):
    gen, rec = plain
    real, calls, nap = gen._emit_round, [], 0.5

    def slow(p):
        calls.append(p.rid)
        if len(calls) == 4:
            time.sleep(nap)
        return real(p)

    before, n0 = gen.perf_stats()["rounds"], len(rec.snapshot(etype="stall"))
    monkeypatch.setattr(gen, "_emit_round", slow)
    out = gen.generate("every shape the tests below meet", max_tokens=40, temperature=0.0)
    monkeypatch.setattr(gen, "_emit_round", real)
    assert out["usage"]["completion_tokens"] == 40 and len(calls) == 10
    after = gen.perf_stats()["rounds"]
    a, b = before["stalls"], after["stalls"]
    assert b["count"] - a["count"] == 1  # one stall, and nothing for the rounds around it
    assert b["by_phase"]["emit"][0] - a["by_phase"].get("emit", [0, 0.0])[0] == 1
    excess = b["excess_s"] - a["excess_s"]
    # the interval is the sleep and a round's host work, its excess that less a mean round
    assert nap - 0.02 <= excess <= nap + 0.15 and b["seconds"] - a["seconds"] >= excess
    events = [e["fields"] for e in rec.snapshot(etype="stall")][n0:]
    assert events == [b["recent"][-1]]
    assert events[0]["phase"] == "emit" and events[0]["program"] == "plain" and events[0]["rid"] == calls[3] + 1
    assert events[0]["wait_s"] < 0.1 and time.monotonic() - 30 < events[0]["t"] <= time.monotonic()
    # the round that closed the stall was fetched and told nothing of it
    rows = {k: after["by_program"]["plain"][k] - before["by_program"]["plain"][k] for k in ("rounds", "device_s")}
    assert rows["rounds"] == len(calls) and rows["device_s"] < nap


def test_a_program_run_to_its_end_between_two_retirements_is_no_stall(monkeypatch):
    """Decode ends, a chunk group of 0.3 s follows STANDALONE without an idle
    wait (the device busy, nothing in flight), then the prompt's own rounds:
    those seconds lie between two retirements and are no stall."""
    monkeypatch.setenv("TPU_SPEC", "0")
    gen = GenerationEngine("tiny-llm", max_slots=2, max_seq_len=512, dtype=jnp.float32, decode_chunk=4,
                           prefill_chunk=8).start()
    acct, real, armed, slept = gen._perf.rounds, gen._dispatch_prefill_group, [], []

    def standalone(group):
        # the retirement before it still heads the chain: the loop has not been idle since the last round's end
        armed.append(acct._prev is not None)
        return real(group)

    def slow(op):
        def run(*a):
            if armed and armed[-1] and not slept:
                slept.append(time.sleep(0.3))  # inside the dispatch: the device's seconds
            return op(*a)
        return run

    long = "a prompt of many chunks that outlasts the row decoding beside it " * 6
    try:
        _drain(_submit(gen, long, max_tokens=8))  # the shapes of a standalone group and of a lone row's rounds
        monkeypatch.setattr(gen, "_dispatch_prefill_group", standalone)
        for op in ("chunk", "ragged"):
            monkeypatch.setitem(gen._ops, op, slow(gen._ops[op]))
        for _ in range(6):  # until the short row was still decoding when the long prompt came, every shape warm
            del slept[:]
            n0 = acct.stats()["stalls"]["count"]
            short = _submit(gen, "a short row", max_tokens=12)
            _wait_active(gen, 1)
            for r in (_submit(gen, long, max_tokens=8), short):
                _drain(r)
            st = acct.stats()["stalls"]
            booked = st["recent"][len(st["recent"]) - (st["count"] - n0):]
            # (a shape met for the first time compiles for longer than 0.2 s on the CPU: that stall is its own)
            if slept and not any(r["phase"] == "first_dispatch" for r in booked):
                break
        else:
            raise AssertionError("the group never ran standalone behind a retirement with every shape warm")
    finally:
        gen.shutdown()
    assert armed.count(True) >= 1 and booked == []


def test_the_rooflines_device_keys_are_the_accounts_totals(plain):
    gen, _rec = plain
    gen.generate("every shape the tests below meet", max_tokens=20, temperature=0.0)
    st = gen.perf_stats()
    rf, tot = st["roofline"], gen._perf.rounds.totals()
    assert tot["told"] >= 1 and not hasattr(gen._perf, "_told")
    assert rf["device_rounds"] == tot["told"] and rf["device_tokens"] == tot["told_tokens"]
    assert rf["device_s"] == round(tot["device_s"], 6)
    assert rf["device_tok_per_s"] == round(tot["told_tokens"] / tot["device_s"], 1)
    assert rf["rows_mean"] == round(tot["told_rows"] / tot["told"], 2)
    # a run of plain rounds: the one row holds them all, as the one sum did
    assert set(st["rounds"]["by_program"]) == {"plain"}
    assert st["rounds"]["by_program"]["plain"]["told"] == rf["device_rounds"]


def test_a_dispatch_names_its_program_and_a_first_dispatch_itself_on_the_host_plane(monkeypatch):
    from llm_mcp_tpu.executor import engine as engine_mod

    log, real = [], engine_mod.TraceAnnotation

    class Noted:
        def __init__(self, name, **kw):
            self.name, self.kw, self.inner = name, kw, real(name, **kw)

        def __enter__(self):
            log.append(("in", self.name, self.kw))
            return self.inner.__enter__()

        def __exit__(self, *exc):
            log.append(("out", self.name, self.kw))
            return self.inner.__exit__(*exc)

    monkeypatch.setattr(engine_mod, "TraceAnnotation", Noted)
    monkeypatch.setenv("TPU_SPEC", "0")
    gen = GenerationEngine("tiny-llm", max_slots=4, max_seq_len=64, dtype=jnp.float32, decode_chunk=4).start()
    try:
        gen.generate("a fresh engine dispatches every shape for the first time", max_tokens=10, temperature=0.0)
        gen.generate("and the decode round not the second time", max_tokens=10, temperature=0.0)
    finally:
        gen.shutdown()
        monkeypatch.setattr(engine_mod, "TraceAnnotation", real)
    firsts = [(i, kw["key"]) for i, (kind, name, kw) in enumerate(log)
              if kind == "in" and name == "engine.first_dispatch"]
    assert {key.split(":")[0] for _i, key in firsts} == {"admit", "decode"}
    assert sum(key.startswith("decode:") for _i, key in firsts) == 1  # one shape, first dispatched once
    for i, key in firsts:  # inside the loop phase that made it, around nothing but the call
        opened = [name for kind, name, _kw in log[:i] if kind == "in"]
        closed = [name for kind, name, _kw in log[:i] if kind == "out"]
        phase = "engine.admit" if key.startswith("admit") else "engine.dispatch"
        assert opened.count(phase) - closed.count(phase) == 1
    dispatches = [kw for kind, name, kw in log if kind == "in" and name == "engine.dispatch"]
    assert dispatches and all(kw["prog"] == "plain" and kw["rid"] >= 1 for kw in dispatches)
    assert [kw["rid"] for kw in dispatches] == sorted(kw["rid"] for kw in dispatches)
