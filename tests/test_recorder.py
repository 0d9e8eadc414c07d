"""Flight recorder, anomaly detectors, and the compile ledger: ring/dump
unit behavior, detector latch + re-arm state machines, synthetic anomaly
fixtures producing exactly one journal each, a threaded append-vs-dump soak
(the ring never blocks an appender), the TPU_FLIGHT=0 true-no-op contract,
the stdlib-only import-direction lint, and the e2e acceptance shape: a real
chat completion lands recorder events whose trace ids resolve against
/v1/traces, /v1/debug/compiles reports cold-boot wall times per bucket, and
an injected decode stall journals the ring exactly once."""

import json
import os
import threading
import time

import httpx
import jax.numpy as jnp
import pytest

from llm_mcp_tpu.api.server import CoreServer
from llm_mcp_tpu.executor import GenerationEngine
from llm_mcp_tpu.state.db import Database
from llm_mcp_tpu.telemetry import recorder as flight
from llm_mcp_tpu.telemetry.recorder import (
    AnomalyMonitor,
    CompileLedger,
    DecodeStallDetector,
    FlightRecorder,
    PagedLeakDetector,
    PingPongDetector,
    ShedDuringGraceDetector,
    SpecCollapseDetector,
    TTFTBurnDetector,
)
from llm_mcp_tpu.utils.config import Config

# ---------------------------------------------------------------------------
# ring buffer units
# ---------------------------------------------------------------------------


def _rec(tmp_path, **kw):
    kw.setdefault("capacity", 64)
    kw.setdefault("dump_dir", str(tmp_path))
    kw.setdefault("dump_interval_s", 0.0)
    return FlightRecorder(**kw)


def test_ring_wrap_keeps_newest_in_seq_order(tmp_path):
    rec = _rec(tmp_path, capacity=16)
    for i in range(40):
        rec.event("decode", rows=i)
    rows = rec.snapshot()
    assert len(rows) == 16  # oldest 24 overwritten
    seqs = [r["seq"] for r in rows]
    assert seqs == sorted(seqs) and seqs[-1] == 39 and seqs[0] == 24
    assert rec.events_total() == 40
    assert rec.dropped_events == 0


def test_snapshot_limit_and_etype_filter(tmp_path):
    rec = _rec(tmp_path)
    for i in range(10):
        rec.event("decode" if i % 2 else "chunk", i=i)
    assert len(rec.snapshot(limit=3)) == 3
    chunks = rec.snapshot(etype="chunk")
    assert len(chunks) == 5 and all(r["etype"] == "chunk" for r in chunks)
    assert chunks[0]["fields"] == {"i": 0}
    # trace id rides along
    rec.event("admit", trace_id="a" * 32, slot=1)
    assert rec.snapshot(etype="admit")[0]["trace_id"] == "a" * 32


def test_frozen_ring_counts_drops_instead_of_blocking(tmp_path):
    rec = _rec(tmp_path)
    rec.event("decode")
    rec._frozen = True
    rec.event("decode")
    rec.event("decode")
    assert rec.dropped_events == 2
    assert rec.events_total() == 1  # frozen appends never landed
    rec._frozen = False
    rec.event("decode")
    assert rec.events_total() == 2


def test_dump_format_rate_limit_and_callbacks(tmp_path):
    rec = _rec(tmp_path, dump_interval_s=3600.0)
    for i in range(5):
        rec.event("verify", trace_id="b" * 32, drafted=4, accepted=i)
    seen = []
    rec.add_dump_callback(seen.append)
    path = rec.dump("unit test", detector="spec_collapse", force=True)
    assert path and os.path.exists(path)
    lines = [json.loads(ln) for ln in open(path, encoding="utf-8")]
    header, events = lines[0], lines[1:]
    assert header["kind"] == "flight_dump"
    assert header["reason"] == "unit test"
    assert header["detector"] == "spec_collapse"
    assert header["events"] == 5 and header["capacity"] == rec.capacity
    assert len(events) == 5
    assert set(events[0]) == {"seq", "ts", "etype", "trace_id", "fields"}
    assert events[-1]["fields"] == {"drafted": 4, "accepted": 4}
    # callback fired with the journal info
    assert len(seen) == 1 and seen[0]["path"] == path
    # rate limit: second non-forced dump inside the interval is suppressed
    assert rec.dump("again") is None
    assert rec.dump("again", force=True) is not None
    assert rec.stats()["dumps"] == 2
    # broken callbacks never break dumps
    rec.add_dump_callback(lambda info: 1 / 0)
    assert rec.dump("cb", force=True) is not None


def test_tpu_flight_0_is_a_true_noop(tmp_path, monkeypatch):
    """TPU_FLIGHT=0: no ring writes, no dumps, no detector state — and the
    knob is dynamic, so flipping it back restores recording on the same
    recorder instance."""
    rec = _rec(tmp_path)
    mon = AnomalyMonitor(rec, target_ttft_ms=100.0)
    monkeypatch.setenv("TPU_FLIGHT", "0")
    assert not rec.enabled
    rec.event("decode", rows=1)
    assert rec.events_total() == 0 and rec.dropped_events == 0
    assert rec.dump("nope", force=True) is None
    assert os.listdir(tmp_path) == []
    # a blatant stall signal produces nothing while disabled
    assert mon.signal("decode_stall", gap_s=999.0, ema_s=0.01, busy=4) is None
    assert mon.stats()["dumps_total"] == 0
    monkeypatch.setenv("TPU_FLIGHT", "1")
    rec.event("decode", rows=1)
    assert rec.events_total() == 1
    assert mon.signal("decode_stall", gap_s=999.0, ema_s=0.01, busy=4)


# ---------------------------------------------------------------------------
# detector state machines: latch on the rising edge, re-arm on recovery
# ---------------------------------------------------------------------------


def test_decode_stall_latch_and_rearm():
    d = DecodeStallDetector(min_gap_s=2.0, ema_mult=20.0)
    assert d.observe(gap_s=10.0, ema_s=0.01, busy=0) is None  # idle ≠ stall
    assert d.observe(gap_s=1.0, ema_s=0.01, busy=3) is None  # under floor
    # big batches move slowly: gap below 20× EMA is not a stall
    assert d.observe(gap_s=3.0, ema_s=0.5, busy=3) is None
    reason = d.observe(gap_s=11.0, ema_s=0.5, busy=3)
    assert reason and "11.00s" in reason
    assert d.observe(gap_s=12.0, ema_s=0.5, busy=3) is None  # latched
    assert d.observe(gap_s=0.1, ema_s=0.5, busy=3) is None  # recovery re-arms
    assert d.observe(gap_s=11.0, ema_s=0.5, busy=3)  # second episode


def test_ttft_burn_needs_k_consecutive():
    d = TTFTBurnDetector(target_ms=100.0, mult=3.0, k=4)
    for _ in range(3):
        assert d.observe(ttft_ms=1000.0) is None
    assert d.observe(ttft_ms=200.0) is None  # good sample resets the streak
    for _ in range(3):
        assert d.observe(ttft_ms=1000.0) is None
    assert d.observe(ttft_ms=1000.0)  # 4th consecutive fires
    assert d.observe(ttft_ms=1000.0) is None  # latched
    assert d.observe(ttft_ms=150.0) is None  # re-arm
    # no SLO configured → never fires
    assert TTFTBurnDetector(target_ms=0.0).observe(ttft_ms=1e9) is None


def test_spec_collapse_windowed_rate():
    d = SpecCollapseDetector(window=8, min_rate=0.05, min_drafted=64)
    assert d.observe(drafted=0, accepted=0) is None  # no draft, no sample
    assert d.observe(drafted=32, accepted=0) is None  # under min_drafted
    reason = d.observe(drafted=40, accepted=1)  # 1/72 ≈ 1.4%
    assert reason and "collapse" in reason
    assert d.observe(drafted=40, accepted=0) is None  # latched
    # healthy rounds push the window rate back up and re-arm
    for _ in range(8):
        d.observe(drafted=40, accepted=30)
    assert d.observe(drafted=40, accepted=0) is None  # rate still healthy
    d2 = SpecCollapseDetector(window=4, min_rate=0.05, min_drafted=8)
    assert d2.observe(drafted=100, accepted=1)


def test_paged_leak_fires_only_on_growth():
    d = PagedLeakDetector()
    assert d.observe(leak_count=0) is None
    reason = d.observe(leak_count=3)
    assert reason and "0 -> 3" in reason
    assert d.observe(leak_count=3) is None  # stable nonzero: no re-fire
    assert d.observe(leak_count=5)  # further growth
    assert d.observe(leak_count=0) is None  # repaired: high-water resets
    assert d.observe(leak_count=2)


def test_pingpong_window_and_eviction():
    d = PingPongDetector(max_hops=2, window_s=60.0, max_tracked=4)
    t = 1000.0
    assert d.observe("r1", now=t) is None
    assert d.observe("r1", now=t + 1) is None
    reason = d.observe("r1", now=t + 2)  # 3rd hop in 60s
    assert reason and "r1" in reason
    assert d.observe("r1", now=t + 3) is None  # fired once per request
    # hops outside the window don't count
    assert d.observe("r2", now=t) is None
    assert d.observe("r2", now=t + 100) is None
    assert d.observe("r2", now=t + 101) is None
    # tracking is bounded: old requests are evicted, not leaked
    for i in range(10):
        d.observe(f"fill-{i}", now=t + 200)
    assert len(d._hops) <= 4


def test_shed_in_grace_one_fire_per_episode():
    d = ShedDuringGraceDetector()
    assert d.observe(in_grace=False, shed=5) is None  # shed outside grace: fine
    assert d.observe(in_grace=True, shed=0) is None
    assert d.observe(in_grace=True, shed=2)
    assert d.observe(in_grace=True, shed=9) is None  # latched for the episode
    assert d.observe(in_grace=False, shed=0) is None  # grace ended
    assert d.observe(in_grace=True, shed=1)  # next episode


# ---------------------------------------------------------------------------
# anomaly monitor: synthetic fixtures → exactly one dump each
# ---------------------------------------------------------------------------


def test_synthetic_anomalies_journal_exactly_once(tmp_path):
    rec = _rec(tmp_path, capacity=128)
    mon = AnomalyMonitor(rec, target_ttft_ms=100.0)
    fired = []
    mon.add_callback(fired.append)
    for i in range(6):
        rec.event("decode", trace_id="c" * 32, rows=2, i=i)

    # stall: repeated polls of the same episode fire once
    for _ in range(5):
        mon.signal("decode_stall", gap_s=30.0, ema_s=0.01, busy=2)
    # SLO burn: 4 consecutive 10× samples
    for _ in range(5):
        mon.signal("ttft_burn", ttft_ms=1000.0)
    # ping-pong: 3 imports of one request inside the window
    now = time.time()
    for k in range(4):
        mon.signal("migration_pingpong", request_id="req-pp", now=now + k)

    st = mon.stats()
    assert st["by_detector"] == {
        "decode_stall": 1, "ttft_burn": 1, "migration_pingpong": 1,
    }
    assert st["dumps_total"] == 3 and len(fired) == 3
    assert st["last"]["detector"] == "migration_pingpong"
    hist = mon.history()
    assert len(hist) == 3 and hist[0] is not hist[-1]
    for entry in hist:
        assert entry["journal"] and os.path.exists(entry["journal"])
        lines = [json.loads(ln) for ln in open(entry["journal"], encoding="utf-8")]
        assert lines[0]["kind"] == "flight_dump"
        assert lines[0]["detector"] == entry["detector"]
        # the journal carries the request events that preceded the anomaly
        assert any(r.get("trace_id") == "c" * 32 for r in lines[1:])
    # each fire also stamps an anomaly event into the ring itself
    assert len(rec.snapshot(etype="anomaly")) == 3

    # unknown kinds and malformed signals are no-ops, not crashes
    assert mon.signal("nonsense", x=1) is None
    assert mon.signal("decode_stall", wrong_kwarg=1) is None
    assert mon.stats()["dumps_total"] == 3


def test_anomaly_dump_respects_rate_limit(tmp_path):
    """Two different detectors inside one dump interval: both land in the
    history, but only the first journals (the second records journal="")."""
    rec = _rec(tmp_path, dump_interval_s=3600.0)
    mon = AnomalyMonitor(rec, target_ttft_ms=100.0)
    mon.signal("decode_stall", gap_s=30.0, ema_s=0.01, busy=2)
    mon.signal("shed_in_grace", in_grace=True, shed=3)
    hist = mon.history()
    assert len(hist) == 2
    journals = [h["journal"] for h in hist]
    assert sum(1 for j in journals if j) == 1


# ---------------------------------------------------------------------------
# append-vs-dump soak: the hot path never blocks on a dump
# ---------------------------------------------------------------------------


def test_append_vs_dump_soak(tmp_path):
    n = 50_000
    rec = _rec(tmp_path, capacity=4096)
    done = threading.Event()

    def appender():
        for i in range(n):
            rec.event("decode", rows=8, i=i)
        done.set()

    t = threading.Thread(target=appender, daemon=True)
    t.start()
    dumps = 0
    while not done.is_set() and dumps < 200:
        if rec.dump("soak", force=True):
            dumps += 1
    t.join(timeout=30.0)
    # the appender finished: it was never blocked by the dump freezes
    assert done.is_set() and not t.is_alive()
    assert dumps > 0
    # conservation: every append either landed (monotonic seq) or was
    # counted as dropped during a freeze window — none vanished
    assert rec.events_total() + rec.dropped_events == n
    # journals on disk are well-formed under concurrency
    last = sorted(p for p in os.listdir(tmp_path) if p.startswith("flight-"))[-1]
    lines = [json.loads(ln) for ln in open(tmp_path / last, encoding="utf-8")]
    assert lines[0]["kind"] == "flight_dump"
    seqs = [r["seq"] for r in lines[1:]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


# ---------------------------------------------------------------------------
# compile ledger
# ---------------------------------------------------------------------------


def test_compile_ledger_aggregates_and_explicit_hit():
    led = CompileLedger()
    e1 = led.observe("decode", "4:4", 1.5, hit=False)
    assert e1["hit"] is False
    e2 = led.observe("decode", "4:4", 0.01, hit=True)
    assert e2["hit"] is True
    # nobody said, and no wall decides: 0.01 s is not a hit, 5 s not a miss
    assert led.observe("chunk", "8:256", 0.8)["hit"] is None
    assert led.observe("chunk", "8:256", 5.0, hit=True)["hit"] is True
    table = led.table()
    assert [r["key"] for r in table] == ["8:256", "4:4"]  # costliest first
    agg = table[1]
    assert agg["count"] == 2 and agg["hits"] == 1 and agg["misses"] == 1
    assert agg["total_s"] == pytest.approx(1.51)
    assert agg["max_s"] == pytest.approx(1.5)
    assert table[0]["hits"] == 1 and table[0]["misses"] == 0  # one unknown
    st = led.stats()
    parts = st.pop("parts")
    assert st == {
        "entries": 4, "hits": 2, "misses": 1, "shapes": 2,
        "total_s": pytest.approx(7.31), "by_src": {"serve": 4},
    }
    assert parts["serve"]["entries"] == 4
    assert parts["serve"]["wall_s"] == pytest.approx(7.31)
    assert len(led.entries(limit=2)) == 2


def test_compile_ledger_files_what_jax_reported_by_source():
    """`parts` is what executor/compile_watch.py hands in: seconds by part,
    the number of compile requests and JAX's own hit; the sums are kept by
    source, in the table's rows and in stats()."""
    led = CompileLedger()
    hit = {"trace_s": 0.4, "lower_s": 0.3, "backend_s": 1.1, "cache_load_s": 0.9,
           "compile_requests": 2, "hit": True}
    miss = dict(hit, backend_s=30.0, cache_load_s=0.0, hit=False)
    e = led.observe("decode", "32:False:True", 2.0, parts=hit)
    assert e["hit"] is True and e["trace_s"] == 0.4 and e["compile_requests"] == 2
    assert e["t"] <= time.monotonic() and e["src"] == "serve"
    led.observe("decode", "32:False:True", 31.0, src="warmup", parts=miss)
    assert led.observe("admit", "1:64", 0.002, parts={"hit": None})["hit"] is None
    assert led.observe("admit", "1:64", 0.5, hit=False, parts=hit)["hit"] is False  # explicit wins
    row = next(r for r in led.table() if r["key"] == "32:False:True")
    assert row["hits"] == 1 and row["misses"] == 1 and row["by_src"] == {"serve": 1, "warmup": 1}
    assert row["parts"]["serve"] == {
        "entries": 1, "wall_s": 2.0, "trace_s": 0.4, "lower_s": 0.3,
        "backend_s": 1.1, "cache_load_s": 0.9, "compile_requests": 2.0}
    assert row["parts"]["warmup"]["backend_s"] == 30.0
    st = led.stats()
    assert st["hits"] == 1 and st["misses"] == 2 and st["entries"] == 4
    assert st["parts"]["serve"]["entries"] == 3
    assert st["parts"]["serve"]["trace_s"] == pytest.approx(0.8)
    assert st["parts"]["warmup"]["wall_s"] == 31.0
    row["parts"]["serve"]["wall_s"] = -1.0  # a reader's copy
    assert led.table()[0]["parts"]["serve"]["wall_s"] >= 0


def test_compile_ledger_drain_fresh_exactly_once():
    led = CompileLedger()
    led.observe("admit", "4:64", 0.4)
    led.observe("verify", "4:8:k", 0.6)
    fresh = led.drain_fresh()
    assert [e["phase"] for e in fresh] == ["admit", "verify"]
    assert led.drain_fresh() == []  # drained
    led.observe("decode", "4:4", 0.3)
    assert [e["phase"] for e in led.drain_fresh()] == ["decode"]
    # draining never touches the queryable history
    assert led.stats()["entries"] == 3


# ---------------------------------------------------------------------------
# import-direction lint: recorder.py stays stdlib-only
# ---------------------------------------------------------------------------


def test_recorder_never_imports_executor(tmp_path):
    """The recorder is loaded by file path with stubbed parent packages
    (so package __init__s never run), exercised through a full event→dump
    round trip, and nothing from the serving stack — and no jax or numpy
    — may be in sys.modules. Stub code, exercise snippet, and forbidden
    prefixes are single-sourced from the purity manifest
    (llm_mcp_tpu/analysis/imports_lint.py); the static half of the same
    pin runs in tests/test_analysis.py."""
    from llm_mcp_tpu.analysis.imports_lint import run_probe

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = run_probe("recorder", repo, tmp=str(tmp_path))
    assert proc.returncode == 0, proc.stderr or proc.stdout


# ---------------------------------------------------------------------------
# e2e: real server + engine on the CPU mesh
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def flight_env(tmp_path_factory):
    """Fresh process recorder + ledger, installed BEFORE the engine is built
    (engines capture the references in __init__). dump_interval_s=0 so
    anomaly journals are never rate-limited away in tests."""
    dump_dir = str(tmp_path_factory.mktemp("flight"))
    rec = FlightRecorder(capacity=8192, dump_dir=dump_dir, dump_interval_s=0.0)
    led = CompileLedger()
    prev_rec = flight.set_recorder(rec)
    prev_led = flight.set_compile_ledger(led)
    yield rec, led, dump_dir
    flight.set_recorder(prev_rec)
    flight.set_compile_ledger(prev_led)


@pytest.fixture(scope="module")
def server(flight_env):
    cfg = Config()
    cfg.db_path = ":memory:"
    gen = GenerationEngine(
        "tiny-llm", max_slots=4, max_seq_len=128, dtype=jnp.float32
    ).start()
    srv = CoreServer(
        cfg, db=Database(":memory:"), gen_engines={"tiny-llm": gen}
    ).start("127.0.0.1", 0)
    yield srv
    srv.shutdown()


@pytest.fixture(scope="module")
def base(server):
    return f"http://127.0.0.1:{server.api.port}"


def _chat(base, max_tokens=6, **kw):
    return httpx.post(
        f"{base}/v1/chat/completions",
        json={
            "model": "tiny-llm",
            "messages": [{"role": "user", "content": "flight check"}],
            "max_tokens": max_tokens,
            "temperature": 0,
        },
        timeout=120.0,
        **kw,
    )


def test_chat_completion_lands_flight_events(base, flight_env):
    rec, _, _ = flight_env
    r = _chat(base)
    assert r.status_code == 200
    tid = r.headers.get("x-trace-id")
    assert tid and len(tid) == 32

    # per-request events (admit) are stamped with this request's trace id;
    # round events (decode, budget) are engine-global. Decode rounds may
    # land just after the response unblocks, so poll briefly.
    deadline = time.monotonic() + 10.0
    mine, etypes = [], set()
    while time.monotonic() < deadline:
        doc = httpx.get(f"{base}/v1/debug/flight?limit=1000").json()
        mine = [e for e in doc["events"] if e["trace_id"] == tid]
        etypes = {e["etype"] for e in doc["events"]}
        if mine and "decode" in etypes:
            break
        time.sleep(0.05)
    assert any(e["etype"] == "admit" for e in mine), sorted(etypes)
    assert "decode" in etypes, sorted(etypes)
    assert doc["recorder"]["enabled"] is True
    assert doc["recorder"]["events_total"] > 0
    # etype filter works over the wire
    doc2 = httpx.get(f"{base}/v1/debug/flight?limit=50&etype=admit").json()
    assert doc2["events"] and all(e["etype"] == "admit" for e in doc2["events"])
    assert httpx.get(f"{base}/v1/debug/flight?limit=bogus").status_code == 400


def test_manual_dump_stitches_into_traces(base, flight_env):
    _, _, dump_dir = flight_env
    tid = _chat(base).headers["x-trace-id"]
    doc = httpx.get(f"{base}/v1/debug/flight?dump=1&limit=10").json()
    path = doc.get("dump_path")
    assert path and os.path.exists(path) and path.startswith(dump_dir)
    lines = [json.loads(ln) for ln in open(path, encoding="utf-8")]
    assert lines[0]["kind"] == "flight_dump" and lines[0]["reason"] == "manual"
    tids = {r["trace_id"] for r in lines[1:] if r.get("trace_id")}
    assert tid in tids
    # every lane in the journal resolves against /v1/traces
    assert httpx.get(f"{base}/v1/traces/{tid}").status_code == 200


def test_compile_ledger_reports_cold_boot_walls(base):
    doc = httpx.get(f"{base}/v1/debug/compiles").json()
    assert doc["stats"]["entries"] > 0
    assert doc["table"], "cold boot must have compiled at least one bucket"
    phases = {r["phase"] for r in doc["table"]}
    assert "decode" in phases, sorted(phases)
    for row in doc["table"]:
        assert row["count"] >= 1 and row["total_s"] > 0 and row["key"]
    # costliest-first ordering
    totals = [r["total_s"] for r in doc["table"]]
    assert totals == sorted(totals, reverse=True)
    for e in doc["entries"]:
        assert e["wall_s"] > 0 and e["phase"] and e["key"]
    # hit or miss is JAX's own answer now, and depends on what the tests'
    # persistent cache already holds; what every first dispatch on the
    # engine's thread has is a trace and a lowering that JAX timed inside it
    assert doc["stats"]["hits"] + doc["stats"]["misses"] <= doc["stats"]["entries"]
    decode = [e for e in doc["entries"] if e["phase"] == "decode"]
    assert decode and all(e["src"] == "serve" for e in decode), decode  # TPU_WARMUP=0 here
    assert all(e["trace_s"] > 0 and e["lower_s"] > 0 for e in decode), decode
    for e in doc["entries"]:
        assert e["trace_s"] + e["lower_s"] + e["backend_s"] <= e["wall_s"] * 1.05 + 1e-3
        assert e["hit"] in (True, False, None)
        assert (e["hit"] is None) == (e["compile_requests"] == 0)
    parts = doc["stats"]["parts"]
    assert parts["serve"]["entries"] == doc["stats"]["entries"]
    assert parts["serve"]["wall_s"] == pytest.approx(doc["stats"]["total_s"], rel=1e-3)
    assert parts["serve"]["trace_s"] == pytest.approx(
        sum(e["trace_s"] for e in doc["entries"]), rel=1e-3)


def test_injected_decode_stall_journals_once(base, server, flight_env):
    """The acceptance fixture: force a decode-cadence stall on the live
    engine and assert exactly one anomaly journal lands, carrying trace ids
    that resolve against /v1/traces. The injection backdates the engine's
    last-round timestamp while a real request is decoding so the genuine
    check_anomalies() path fires; if the tiny CPU generation outruns the
    injection loop, the same signal is driven through the engine's monitor
    directly (identical dump path)."""
    rec, _, _ = flight_env
    eng = server.gen_engines["tiny-llm"]
    tid = _chat(base).headers["x-trace-id"]
    before = eng._anomaly.stats()["by_detector"].get("decode_stall", 0)

    hit = threading.Event()

    def inject():
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and not hit.is_set():
            busy = sum(1 for s in eng._slots if s is not None)
            if busy > 0:
                eng._compile_grace_until = 0.0
                eng._last_round_ts = time.time() - 100.0
                eng.check_anomalies()
                if eng._anomaly.stats()["by_detector"].get(
                    "decode_stall", 0
                ) > before:
                    hit.set()
                    return
            time.sleep(0.002)

    t = threading.Thread(target=inject, daemon=True)
    t.start()
    _chat(base, max_tokens=48)
    t.join(timeout=25.0)
    if not hit.is_set():
        # generation finished before the injector saw a busy slot: drive the
        # detector through the engine's own monitor instead
        eng._anomaly.signal("decode_stall", gap_s=120.0, ema_s=0.01, busy=2)
    eng._last_round_ts = time.time()  # recover so the detector re-arms cleanly

    hist = [h for h in eng.anomaly_history() if h["detector"] == "decode_stall"]
    assert len(hist) == before + 1, "one stall episode, one dump"
    entry = hist[0]
    assert "stalled" in entry["reason"]
    assert entry["journal"] and os.path.exists(entry["journal"])
    lines = [json.loads(ln) for ln in open(entry["journal"], encoding="utf-8")]
    assert lines[0]["detector"] == "decode_stall"
    tids = {r["trace_id"] for r in lines[1:] if r.get("trace_id")}
    assert tid in tids
    for t32 in list(tids)[:3]:
        assert httpx.get(f"{base}/v1/traces/{t32}").status_code == 200
    # the anomaly surfaces through the API layers too
    doc = httpx.get(f"{base}/v1/debug/flight?limit=10").json()
    assert doc["anomalies"]["tiny-llm"], "per-engine anomaly history exposed"
    fs = eng.flight_stats()
    assert fs["anomaly"]["by_detector"]["decode_stall"] >= 1
    assert fs["dumps"] >= 1 and fs["last_dump_path"]


def test_watchdog_transitions_and_metrics_bridge(base, server):
    """Cold boot opened at least one compile-grace episode; the transition
    counts surface in flight_stats and the Prometheus families appear on
    /metrics (the scrape itself refreshes the delta bridges)."""
    eng = server.gen_engines["tiny-llm"]
    fs = eng.flight_stats()
    assert fs["watchdog_transitions"].get("compile_grace", 0) >= 1
    assert fs["compile"]["entries"] > 0
    text = httpx.get(f"{base}/metrics").text
    assert "llmtpu_flight_events_total" in text
    assert "llmtpu_compile_seconds" in text
    assert "llmtpu_watchdog_transitions_total" in text
    assert "llmtpu_anomaly_dumps_total" in text
    assert "llmtpu_flight_dropped_events" in text


def test_dashboard_carries_anomaly_and_compile_blocks(base, server):
    eng = server.gen_engines["tiny-llm"]
    if not eng._anomaly.stats()["by_detector"].get("decode_stall"):
        # under `--dist load` the stall test above may have run in another
        # worker, with a server of its own: one episode of this worker's own
        eng._anomaly.signal("decode_stall", gap_s=120.0, ema_s=0.01, busy=2)
    if not eng.flight_stats()["compile"]["entries"]:  # and a first dispatch of its own
        _chat(base, max_tokens=4)
    doc = httpx.get(f"{base}/v1/dashboard").json()
    assert "anomalies" in doc and "compiles" in doc
    eng = doc["anomalies"]["tiny-llm"]
    assert eng["dumps"] >= 1 and "decode_stall" in eng["by_detector"]
    assert doc["compiles"]["top"], "costliest compile shapes listed"
    # the recent injected stall surfaces as a dashboard issue
    assert any("anomaly in the last" in i for i in doc["issues"]), doc["issues"]


def test_profile_endpoints(base):
    doc = httpx.get(f"{base}/v1/debug/profile").json()
    assert "tiny-llm" in doc
    assert set(doc["tiny-llm"]) == {
        "active", "steps_left", "pending_steps", "trace_dir",
    }
    r = httpx.post(f"{base}/v1/debug/profile", json={"engine": "no-such"})
    assert r.status_code == 404
