"""The engine's clock inside the program (PR 24): the round's life in the
flight ring (dispatch, fetch, emit on time.monotonic()), whole event gaps and
stream lags in the perf observatory, first dispatches taken apart by JAX's
own timers and kept out of the scheduler's cost model, the embedding
engine's counters. On the CPU at a tiny size: counts and clocks' ORDER, never
a rate."""

import concurrent.futures as cf
import json
import time

import httpx
import jax.numpy as jnp
import pytest

from llm_mcp_tpu.api.server import CoreServer
from llm_mcp_tpu.executor import EmbeddingEngine, GenerationEngine, compile_watch
from llm_mcp_tpu.state.db import Database
from llm_mcp_tpu.telemetry import recorder as flight
from llm_mcp_tpu.telemetry.perf import SAMPLE_KINDS, PerfObservatory
from llm_mcp_tpu.telemetry.recorder import CompileLedger, FlightRecorder
from llm_mcp_tpu.utils.config import Config

K = 4  # decode_chunk


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    """A fresh ring and ledger, installed before the engine is built."""
    rec = FlightRecorder(capacity=8192, dump_dir=str(tmp_path_factory.mktemp("flight")))
    led = CompileLedger()
    prev = flight.set_recorder(rec), flight.set_compile_ledger(led)
    gen = GenerationEngine("tiny-llm", max_slots=4, max_seq_len=128, dtype=jnp.float32,
                           decode_chunk=K).start()
    srv = CoreServer(Config(), db=Database(":memory:"), gen_engines={"tiny-llm": gen}).start("127.0.0.1", 0)
    yield gen, rec, led, f"http://127.0.0.1:{srv.api.port}"
    srv.shutdown()
    flight.set_recorder(prev[0])
    flight.set_compile_ledger(prev[1])


def ring(rec, etype):
    return [e["fields"] for e in rec.snapshot(etype=etype)]


def settle(gen, rec, n_emit):
    """Wait until the loop has emitted `n_emit` rounds and gone idle."""
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if len(ring(rec, "emit")) >= n_emit and all(s is None for s in gen._slots):
            return
        time.sleep(0.02)
    raise AssertionError(f"engine did not settle: {len(ring(rec, 'emit'))} emits")


def test_a_round_is_recorded_at_dispatch_fetch_and_emit(env):
    gen, rec, _led, _base = env
    t0 = time.monotonic()
    # 10 tokens: the first at admission, then 9 from rounds of 4: the third
    # round delivers ONE token of the four it computed (the reply ends there)
    out = gen.generate("the round's life", max_tokens=10, temperature=0.0)
    assert out["usage"]["completion_tokens"] == 10
    settle(gen, rec, 3)
    t1 = time.monotonic()
    disp, fetch, emit = ring(rec, "decode"), ring(rec, "fetch"), ring(rec, "emit")
    rids = [d["rid"] for d in disp]
    assert rids == sorted(rids) and len(rids) >= 3
    assert [f["rid"] for f in fetch] == rids and [e["rid"] for e in emit] == rids
    for d, f, e in zip(disp, fetch, emit):
        # one clock, time.monotonic(), in the order a round lives
        assert t0 <= d["t"] <= f["t"] <= e["t"] <= t1
        assert f["wait_ms"] >= 0 and e["dur_ms"] >= 0
        assert e["rows"] == d["rows"] == 1
        assert 0 <= e["texts"] <= e["rows"] and e["held"] >= 0
    assert [e["delivered"] for e in emit[:3]] == [4, 4, 1]
    assert sum(e["delivered"] for e in emit) == 9
    # rounds dispatched after the reply's end was fetched deliver nothing


def test_decode_token_yield_counts_delivered_over_dispatched(env):
    gen, rec, _led, _base = env
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmark import run as bench_run

    w0 = time.monotonic()
    n_before = len(ring(rec, "emit"))
    gen.generate("yield", max_tokens=6, temperature=0.0)  # 1 + 4 + 1 of 4
    settle(gen, rec, n_before + 2)
    run = {"sut": {"gen": gen}, "window_abs": (w0, time.monotonic())}
    emits = [e for e in ring(rec, "emit") if e["t"] >= w0]
    rows, delivered = sum(e["rows"] for e in emits), sum(e["delivered"] for e in emits)
    assert delivered == 5 and rows >= 2
    reader = bench_run.load_reader("layer_metrics", "decode_token_yield")
    assert reader.read(run) == pytest.approx(100.0 * delivered / (rows * K))
    assert reader.read(run) <= 100.0 * 5 / 8
    assert reader.read(dict(run, window_abs=(0.0, 1e-9))) is None  # nothing in the window


def test_event_gap_is_whole_and_stream_lag_survives_the_handler(env):
    gen, rec, _led, base = env
    before = {k: len(gen._perf.samples(k)) for k in SAMPLE_KINDS}
    itl_before = gen.perf_stats()["itl"]["samples"]
    events, stamps = [], []
    orig = gen.observe_stream_write
    gen.observe_stream_write = lambda t: (stamps.append(t), orig(t))[1]
    t0 = time.monotonic()
    try:
        with httpx.stream("POST", f"{base}/v1/chat/completions", timeout=120.0, json={
                "model": "tiny-llm", "stream": True, "max_tokens": 17, "temperature": 0,
                "messages": [{"role": "user", "content": "gaps"}]}) as r:
            for line in r.iter_lines():
                if line.startswith("data: {"):
                    delta = json.loads(line[6:])["choices"][0]["delta"]
                    if delta.get("content"):
                        events.append(delta["content"])
    finally:
        gen.observe_stream_write = orig
    t1 = time.monotonic()
    gaps = gen._perf.samples("event_gap")[before["event_gap"]:]
    lags = gen._perf.samples("stream_lag")[before["stream_lag"]:]
    # one lag a frame written, each from a stamp the engine put on the event
    assert len(lags) == len(events) == len(stamps) >= 3
    assert all(t0 <= s <= t1 for s in stamps) and stamps == sorted(stamps)
    assert all(t0 <= t <= t1 and 0 <= lag < t1 - t0 for t, lag in lags)
    # one gap between successive text events of the stream, whole
    assert len(gaps) == len(events) - 1
    assert [round(g, 6) for _t, g in gaps] == [round(b - a, 6) for a, b in zip(stamps, stamps[1:])]
    # the ITL window spreads the same gaps over each round's tokens
    itl = gen.perf_stats()["itl"]
    assert itl["samples"] - itl_before == 16
    assert sum(g for _t, g in gaps) > 0
    doc = httpx.get(f"{base}/v1/debug/perf").json()["tiny-llm"]
    assert doc["event_gap"]["samples"] >= len(gaps) and doc["stream_lag"]["samples"] >= len(lags)
    assert doc["event_gap"]["p95_ms"] >= doc["event_gap"]["p50_ms"] > 0


def test_observe_sample_is_a_timestamped_bounded_window():
    obs = PerfObservatory()
    t0 = time.monotonic()
    obs.observe_sample("event_gap", 0.25)
    obs.observe_sample("event_gap", -1.0)  # clamped
    obs.observe_sample("no_such_kind", 1.0)  # dropped
    (ta, a), (tb, b) = obs.samples("event_gap")
    assert (a, b) == (0.25, 0.0) and t0 <= ta <= tb <= time.monotonic()
    assert obs.samples("stream_lag") == [] and obs.samples("no_such_kind") == []
    assert obs.sample_percentiles("event_gap") == {"p50_ms": 0.0, "p95_ms": 250.0, "samples": 2.0}
    assert obs._samples["event_gap"].maxlen >= 8192


def test_device_seconds_are_told_at_the_fetch_with_their_own_tokens(env, monkeypatch):
    gen, rec, _led, _base = env
    monkeypatch.setenv("TPU_PERF_SAMPLE", "1")
    import inspect

    assert "block_until_ready" not in inspect.getsource(GenerationEngine._dispatch_decode)
    before = gen.perf_stats()["phases"]["decode"]
    told0 = gen._perf.rounds.totals()
    n = len(ring(rec, "emit"))
    gen.generate("sampled", max_tokens=14, temperature=0.0)
    settle(gen, rec, n + 3)
    after = gen.perf_stats()["phases"]["decode"]
    got = int(after["samples"] - before["samples"])
    assert got >= 1 and after["device_s"] > before["device_s"]
    assert after["tokens"] - before["tokens"] == got * K  # rows x decode_chunk a sample
    perf = [p for p in ring(rec, "perf") if p["phase"] == "decode"][-got:]
    assert len(perf) == got  # one event a sample, told or not
    told = [p for p in perf if p["device_ms"] is not None]
    fetches = {f["rid"]: f for f in ring(rec, "fetch")}
    disp = {d["rid"]: d for d in ring(rec, "decode")}
    # a round's device time lies inside its dispatch-to-fetch span
    spans = [1e3 * (fetches[r]["t"] - disp[r]["t"]) for r in fetches if r in disp]
    assert told and all(0 < p["device_ms"] <= max(spans) + 1.0 for p in told)
    # seconds and tokens of the SAME rounds: with every round sampled, the
    # rounds told are the perf events that carry device seconds
    now = gen._perf.rounds.totals()
    d = {k: now[k] - told0[k] for k in told0}
    assert d["told"] == d["told_rows"] == len(told) and d["told_tokens"] == K * len(told)
    assert d["device_s"] == pytest.approx(after["device_s"] - before["device_s"], abs=1e-5)
    rf = gen.perf_stats()["roofline"]
    assert (rf["device_rounds"], rf["device_tokens"]) == (now["told"], now["told_tokens"])
    assert rf["device_tok_per_s"] == pytest.approx(rf["device_tokens"] / rf["device_s"], rel=1e-3)


def test_first_dispatches_are_taken_apart_and_leave_the_cost_model_alone(env):
    gen, _rec, led, _base = env
    gen.generate("first dispatches", max_tokens=6, temperature=0.0)  # if no test before this one did
    serve = [e for e in led.entries(512) if e["src"] == "serve"]
    assert {"admit", "decode"} <= {e["phase"] for e in serve}
    for e in serve:
        assert e["trace_s"] + e["lower_s"] + e["backend_s"] <= e["wall_s"] * 1.05 + 1e-3
        assert (e["hit"] is None) == (e["compile_requests"] == 0)
    assert all(e["trace_s"] > 0 and e["lower_s"] > 0 for e in serve if e["phase"] == "decode")
    parts = led.stats()["parts"]["serve"]
    assert parts["entries"] == len(serve)
    assert parts["trace_s"] == pytest.approx(sum(e["trace_s"] for e in serve), abs=1e-4)
    # the decode EMA never saw a first dispatch's wall: every compile here
    # took longer than any steady round of the tiny model
    slowest = max(e["wall_s"] for e in serve if e["phase"] in ("decode", "admit"))
    assert gen._sched.decode_round_s < slowest
    assert gen._first_end > 0


def test_a_round_that_holds_a_compile_teaches_the_scheduler_nothing(env):
    gen, _rec, _led, _base = env
    from llm_mcp_tpu.executor.engine import _DispatchedRound
    import numpy as np

    ema = gen._sched.decode_round_s
    cost = gen._sched.prefill_tok_s

    def fake(t0, prefill=0):
        return _DispatchedRound(out=jnp.zeros((K, 4), jnp.int32), entries=[], base=np.zeros(4, np.int32),
                                t0=t0, rid=gen._rid_dispatched, prefill_tokens=prefill, prefill_padded=prefill)

    now = time.perf_counter()
    gen._first_end = now  # a first dispatch ended just now
    gen._complete_round(fake(now - 7.0))  # dispatched before it: 7 s of compile inside
    gen._complete_round(fake(now - 7.0, prefill=64))
    assert (gen._sched.decode_round_s, gen._sched.prefill_tok_s) == (ema, cost)
    gen._first_end = now - 10.0
    gen._complete_round(fake(time.perf_counter() - 0.5))  # a clean round does teach
    assert gen._sched.decode_round_s > ema


def test_a_round_whose_device_time_cannot_be_told_gives_neither_seconds_nor_tokens(env, monkeypatch):
    gen, rec, _led, _base = env
    from llm_mcp_tpu.executor.engine import _DispatchedRound
    import numpy as np

    before = gen.perf_stats()["phases"]["decode"]
    told0 = gen._perf.rounds.totals()
    n_perf = len(ring(rec, "perf"))
    # a sampled round fetched long after it ended (the read does not block)
    late = _DispatchedRound(out=jnp.zeros((K, 4), jnp.int32), entries=[], base=np.zeros(4, np.int32),
                            t0=time.perf_counter() - 0.3, rid=gen._rid_dispatched,
                            t_disp=time.perf_counter() - 0.299, sample=(0.001, 0.0))
    late.out.block_until_ready()
    gen._complete_round(late)
    assert gen.perf_stats()["phases"]["decode"] == before  # no 300 ms of "device" time
    assert gen._perf.rounds.totals() == told0
    assert ring(rec, "perf")[n_perf:] == [
        {"phase": "decode", "host_ms": 1.0, "device_ms": None, "wait_ms": 0.0, "rows": 0}]
    # with sampling far away (every 10,000th round) rounds that can tell still
    # feed the token rate, seconds and tokens together, and count no sample
    monkeypatch.setenv("TPU_PERF_SAMPLE", "10000")
    n = len(ring(rec, "emit"))
    gen.generate("sampled", max_tokens=14, temperature=0.0)  # a reply known to run its 14 tokens
    settle(gen, rec, n + 3)
    after = gen.perf_stats()["phases"]["decode"]
    assert after == before and len(ring(rec, "perf")) == n_perf + 1
    now = gen._perf.rounds.totals()
    d = {k: now[k] - told0[k] for k in told0}
    assert d["told"] >= 1 and d["told_tokens"] == K * d["told"] and 0 < d["device_s"] < 0.25 * d["told"]


class FakeMonitoring:
    """jax.monitoring's two registration calls, kept for the test to fire."""

    def __init__(self):
        self.dur, self.evt = [], []

    def register_event_duration_secs_listener(self, fn):
        self.dur.append(fn)

    def register_event_listener(self, fn):
        self.evt.append(fn)

    def duration(self, name, s, **kw):
        for fn in self.dur:
            fn(name, s, **kw)

    def event(self, name, **kw):
        for fn in self.evt:
            fn(name, **kw)


@pytest.mark.parametrize("requests,hits,want", [(2, 2, True), (2, 1, False), (1, 0, False), (0, 0, None)])
def test_compile_watch_sums_what_jax_reports_on_the_thread(monkeypatch, requests, hits, want):
    import threading

    import jax

    fake = FakeMonitoring()
    monkeypatch.setattr(jax, "monitoring", fake)
    monkeypatch.setattr(compile_watch, "_registered", False)
    assert compile_watch.end() is None  # no context open
    fake_events = lambda: [fake.event("/jax/compilation_cache/compile_requests_use_cache")  # noqa: E731
                           for _ in range(requests)] + [fake.event("/jax/compilation_cache/cache_hits")
                                                        for _ in range(hits)]
    compile_watch.begin()
    assert len(fake.dur) == len(fake.evt) == 1  # registered once, on first use
    fake.duration("/jax/core/compile/jaxpr_trace_duration", 0.002, fun_name="inner")  # nested in ...
    time.sleep(0.005)
    fake.duration("/jax/core/compile/jaxpr_trace_duration", 0.02, fun_name="outer")  # ... this one
    fake.duration("/jax/core/compile/jaxpr_trace_duration", 1e-6, fun_name="helper")  # a second program
    fake.duration("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.3)
    fake.duration("/jax/core/compile/backend_compile_duration", 1.5)
    fake.duration("/jax/compilation_cache/cache_retrieval_time_sec", 1.25)
    fake.duration("/jax/some/other/event", 99.0)
    fake_events()
    # another thread's compile (the warm-up zoo's) is not this dispatch's
    other = threading.Thread(target=lambda: (fake.duration("/jax/core/compile/backend_compile_duration", 40.0),
                                             fake.event("/jax/compilation_cache/cache_hits")))
    other.start()
    other.join()
    parts = compile_watch.end()
    assert parts == {"trace_s": pytest.approx(0.020001, abs=1e-5), "lower_s": 0.3, "backend_s": 1.5,
                     "cache_load_s": 1.25, "compile_requests": requests, "hit": want}
    compile_watch.begin()
    assert len(fake.dur) == 1  # not registered again
    # with no context open nothing is kept, and nothing raises
    assert compile_watch.end()["backend_s"] == 0.0 and compile_watch.end() is None
    fake.duration("/jax/core/compile/backend_compile_duration", 3.0)
    e = CompileLedger().observe("decode", "32:False:True", 2.0, parts=parts)
    assert e["hit"] is want and e["backend_s"] == 1.5


def test_embedding_engine_stats_against_a_hand_count():
    emb = EmbeddingEngine("tiny-embed", max_batch=4, max_seq_len=64, dtype=jnp.float32)
    assert emb.stats() == {"forwards": 0, "ahead": 0, "rows": 0, "rows_packed": 0, "rows_padded": 0,
                           "true_tokens": 0, "padded_tokens": 0, "lock_wait_s": 0.0,
                           "forward_s": 0.0, "host_locked_s": 0.0, "inflight_max": 0, "recent": []}
    # 6 rows over a cap of 4: two EQUAL forwards of 3 rows, both in the 4-row bucket (PR 31)
    texts = ["a" * 10, "b" * 40, "c" * 5, "d" * 20, "e" * 33, "f" * 3]
    lens = [len(emb.prepare_ids(t)) for t in texts]
    t0 = time.monotonic()
    vecs, total = emb.embed(texts, dimensions=8)
    t1 = time.monotonic()
    assert len(vecs) == 6 and len(vecs[0]) == 8 and total == sum(lens)
    st = emb.stats()
    assert (st["forwards"], st["rows"], st["rows_packed"], st["rows_padded"]) == (2, 6, 6, 4 + 4)
    assert st["true_tokens"] == sum(lens) and st["padded_tokens"] == 8 * emb._bucket(max(lens))
    assert len(st["recent"]) == 2
    for t, fwd_s, host_s in st["recent"]:
        assert t0 <= t <= t1 and fwd_s > 0 and host_s > 0
    assert st["forward_s"] == pytest.approx(sum(r[1] for r in st["recent"]))
    assert st["host_locked_s"] == pytest.approx(sum(r[2] for r in st["recent"]))
    assert st["lock_wait_s"] >= 0 and st["forward_s"] + st["host_locked_s"] <= (t1 - t0)
    emb.embed(["one more"])
    assert emb.stats()["forwards"] == 3 and emb.stats()["rows_padded"] == 9


def test_embedding_engine_under_the_benchmarks_tap_still_feeds_every_reader():
    """A traced run wraps `_fwd` in `benchmark/run.py:EmbedTap`, which blocks
    until the forward is ready and reads `tokens.size` and `lengths.sum()`: every
    dispatch then waits as the serial loop did (PR 53: the traced run does not
    show the queue). The tap still sees every forward, `recent` is still
    triples, and the cell's three embedding readers read a number."""
    from benchmark import run as bench_run
    from benchmark.layer_metrics import embed_forward_ms, embed_host_locked_ms, embed_pad_waste_pct

    emb = EmbeddingEngine("tiny-qwen3", max_seq_len=512, dtype=jnp.float32)
    tap = bench_run.EmbedTap(emb)
    texts = [[f"{who} text {n} " * (1 + 5 * n) for n in range(12)] for who in ("one", "two")]
    t0 = time.monotonic()
    with cf.ThreadPoolExecutor(2) as pool:
        got = [f.result(120.0) for f in [pool.submit(emb.embed, t, 16) for t in texts]]
    t1 = time.monotonic()
    assert [len(vecs) for vecs, _ in got] == [12, 12]
    st = emb.stats()
    assert len(tap.calls) == st["forwards"] == len(st["recent"]) >= 2
    assert sum(p for _a, _b, p, _t in tap.calls) == st["padded_tokens"]
    assert sum(t for _a, _b, _p, t in tap.calls) == st["true_tokens"] == sum(n for _, n in got)
    assert st["ahead"] == 0  # the tap hands a forward back when it is ready: none is ahead
    for t, fwd_s, host_s in st["recent"]:
        assert t0 <= t <= t1 and fwd_s >= 0 and host_s > 0
    run = {"sut": {"emb": emb}, "embed_tap": tap, "window_abs": (t0, t1)}
    assert embed_host_locked_ms.read(run) == pytest.approx(1e3 * st["host_locked_s"] / st["forwards"])
    assert embed_forward_ms.read(run) > 0 and 0 < embed_pad_waste_pct.read(run) < 100


def test_admission_reads_are_counted_and_recorded_against_a_hand_count(env):
    """PR 29: an admission's first tokens are read from the in-flight queue.
    Three plain requests one after another and one constrained: four
    `admit_read` events of one row each, the last read where it was
    dispatched; each `after_rid` is the newest round fetched before it."""
    gen, rec, _led, base = env
    keys = ("reads", "reads_blocked", "reads_at_once")
    before = {k: gen.perf_stats()["admit"][k] for k in keys}
    n0 = len(ring(rec, "admit_read"))
    t0 = time.monotonic()
    for i in range(3):
        assert gen.generate(f"count the reads {i}", max_tokens=6, temperature=0.0)["usage"]["completion_tokens"] == 6
    out = gen.generate("heads or tails?", max_tokens=8, temperature=0.0,
                       constraint={"type": "choice", "choices": ["heads", "tails"]})
    assert out["text"] in ("heads", "tails")
    t1 = time.monotonic()
    reads = ring(rec, "admit_read")[n0:]
    assert len(reads) == 4
    for r in reads:
        assert set(r) == {"aid", "rows", "after_rid", "wait_ms", "blocked", "t"}
        assert r["rows"] == 1 and r["wait_ms"] >= 0 and t0 <= r["t"] <= t1 and r["blocked"] in (True, False)
    after = {k: gen.perf_stats()["admit"][k] for k in keys}
    assert after["reads"] - before["reads"] == 4
    assert after["reads_at_once"] - before["reads_at_once"] == 1
    assert after["reads_blocked"] - before["reads_blocked"] == sum(r["blocked"] for r in reads)
    # the ring in the order it was written: a read's after_rid is the last fetch before it
    last_fetch, seen = 0, []
    for e in rec.snapshot():
        if e["etype"] == "fetch":
            last_fetch = e["fields"]["rid"]
        elif e["etype"] == "admit_read":
            seen.append((e["fields"]["after_rid"], last_fetch))
    assert len(seen) >= 4 and all(a == b for a, b in seen[-4:])
    doc = httpx.get(f"{base}/v1/debug/perf").json()["tiny-llm"]
    assert {k: doc["admit"][k] for k in keys} == after


def test_the_admission_blocks_only_under_engine_admit_sync(env, monkeypatch):
    """`engine_host_ms_per_round` subtracts the admission's blocking read by
    the name `engine.admit.sync`, nested in `engine.admit`: one such span an
    admission, around the one device read, and none where it is dispatched."""
    import inspect

    from llm_mcp_tpu.executor import engine as engine_mod

    gen, rec, _led, _base = env
    log, real = [], engine_mod.TraceAnnotation

    class Noted:
        def __init__(self, name, **kw):
            self.name, self.inner = name, real(name, **kw)

        def __enter__(self):
            log.append(("in", self.name))
            return self.inner.__enter__()

        def __exit__(self, *exc):
            log.append(("out", self.name))
            return self.inner.__exit__(*exc)

    n = len(ring(rec, "emit"))
    monkeypatch.setattr(engine_mod, "TraceAnnotation", Noted)
    gen.generate("one admission, one blocking read", max_tokens=6, temperature=0.0)
    settle(gen, rec, n + 2)
    monkeypatch.setattr(engine_mod, "TraceAnnotation", real)
    sync = [i for i, e in enumerate(log) if e == ("in", "engine.admit.sync")]
    assert len(sync) == 1
    opened = [name for kind, name in log[:sync[0]] if kind == "in"]
    closed = [name for kind, name in log[:sync[0]] if kind == "out"]
    assert opened.count("engine.admit") - closed.count("engine.admit") == 1  # nested in the phase
    assert log[sync[0] + 1] == ("out", "engine.admit.sync")
    read_src = inspect.getsource(GenerationEngine._read_admit)
    assert read_src.count("np.asarray(") == 1 and 'TraceAnnotation("engine.admit.sync")' in read_src
    for fn in (GenerationEngine._start_batch, GenerationEngine._seat, GenerationEngine._admit_pending):
        src = inspect.getsource(fn)
        assert "np.asarray(toks0" not in src and "block_until_ready" not in src and ".sync" not in src


@pytest.mark.parametrize("prompts", [1, 3])
def test_an_admit_program_is_recorded_where_it_is_dispatched(env, monkeypatch, prompts):
    """PR 37: one `admit_prog` ring event an admission dispatched, on the one
    clock, and around the dispatch itself an annotation `engine.admit.dispatch`
    that carries the event's `aid`, nested in the `engine.admit` phase: in a
    profiler trace every run of `jit_admit_fn` has the dispatch that caused it."""
    from llm_mcp_tpu.executor import engine as engine_mod

    gen, rec, _led, _base = env
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

    n0, before = len(ring(rec, "admit_prog")), gen.perf_stats()["admit"]
    monkeypatch.setattr(engine_mod, "TraceAnnotation", Noted)
    t0 = time.monotonic()
    texts = [f"prompt number {i} " * (i + 1) for i in range(prompts)]
    with cf.ThreadPoolExecutor(prompts) as pool:
        outs = list(pool.map(lambda p: gen.generate(p, max_tokens=5, temperature=0.0), texts))
    t1 = time.monotonic()
    monkeypatch.setattr(engine_mod, "TraceAnnotation", real)
    assert all(o["usage"]["completion_tokens"] == 5 for o in outs)
    progs = ring(rec, "admit_prog")[n0:]
    after = gen.perf_stats()["admit"]
    assert 1 <= len(progs) <= prompts and sum(f["rows"] for f in progs) == prompts
    for f in progs:
        assert set(f) == {"aid", "kind", "rows", "rows_padded", "bucket", "true_tokens", "padded_tokens",
                          "queued", "held_by", "wait_ms_max", "after_rid", "t"}
        assert t0 <= f["t"] <= t1 and 0 <= f["wait_ms_max"] <= 1e3 * (t1 - t0)
        assert f["padded_tokens"] == f["rows_padded"] * f["bucket"]
    assert sum(f["true_tokens"] for f in progs) == sum(len(gen.tokenizer.encode(p)) for p in texts)
    assert after["true_tokens"] - before["true_tokens"] == sum(f["true_tokens"] for f in progs)
    assert after["programs"] - before["programs"] == len(progs)
    # the annotation: one a program, its aid the event's, inside engine.admit, no device read in it
    spans = [(i, kw["aid"]) for i, (kind, name, kw) in enumerate(log)
             if kind == "in" and name == "engine.admit.dispatch"]
    assert [aid for _i, aid in spans] == [f["aid"] for f in progs]
    for i, _aid in spans:
        opened = [name for kind, name, _kw in log[:i] if kind == "in"]
        closed = [name for kind, name, _kw in log[:i] if kind == "out"]
        assert opened.count("engine.admit") - closed.count("engine.admit") == 1
        # (a shape's first dispatch names itself inside it since PR 54: `_dx` opens `engine.first_dispatch`)
        inside = [e for e in log[i + 1:] if e[1] != "engine.first_dispatch"]
        assert inside[0][:2] == ("out", "engine.admit.dispatch")
