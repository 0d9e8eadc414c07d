"""Workload capture → replay observatory (ISSUE 16): capture ring schema
round-trips, garbage-tolerant trace parsing, seeded synthetic-workload
determinism, deterministic prompt reconstruction, the exact-partition
latency waterfall, the stdlib-only import lint, and the engine e2e
acceptance shape: a finished CPU request produces a waterfall ledger
whose stages sum to within 5% of the measured wall plus a capture record
carrying the prefix-chain digests and (opted in) raw prompt ids."""

import json
import os
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llm_mcp_tpu.telemetry import workload  # noqa: E402
from llm_mcp_tpu.telemetry.workload import (  # noqa: E402
    CHAIN_HEAD,
    SCHEMA_VERSION,
    STAGES,
    LatencyWaterfall,
    WorkloadTrace,
    parse_trace,
    prompt_text_for,
    synth_trace,
)


# ---------------------------------------------------------------------------
# capture ring + trace file round trip


def _record(wl, i=0, **kw):
    args = dict(
        ts=100.0 + i, rid=f"req{i:04d}", trace_id="t" * 32, model="tiny-llm",
        prompt_tokens=32, chain=[(16, "a" * 16), (32, "b" * 16)],
        max_tokens=8, temperature=0.0, top_k=0, top_p=1.0,
        output_tokens=8, finish="length",
    )
    args.update(kw)
    return wl.record(**args)


def test_capture_dump_parse_round_trip(tmp_path):
    wl = WorkloadTrace(capacity=64, trace_path="", include_ids=True)
    recs = [_record(wl, i, ids=[1, 2, 3, i]) for i in range(5)]
    path = tmp_path / "trace.jsonl"
    assert wl.dump(str(path)) == 5
    parsed, rejected = parse_trace(path.read_text().splitlines())
    assert rejected == 0
    assert parsed == recs  # byte-level schema identity through the file


def test_trace_path_streams_records(tmp_path):
    path = tmp_path / "stream.jsonl"
    wl = WorkloadTrace(capacity=8, trace_path=str(path), include_ids=False)
    _record(wl, 0)
    _record(wl, 1)
    parsed, rejected = parse_trace(path.read_text().splitlines())
    assert len(parsed) == 2 and rejected == 0
    assert "ids" not in parsed[0]  # include_ids=False strips raw ids


def test_ring_is_bounded_and_stats_count_everything():
    wl = WorkloadTrace(capacity=16, trace_path="")
    for i in range(40):
        _record(wl, i)
    st = wl.stats()
    assert st["ring"] == 16 and st["records_total"] == 40
    assert wl.snapshot(4)[-1]["rid"] == "req0039"


def test_disabled_knob_is_a_true_noop(monkeypatch):
    monkeypatch.setenv("TPU_WORKLOAD", "0")
    wl = WorkloadTrace(capacity=16, trace_path="")
    assert _record(wl) is None
    assert wl.stats()["records_total"] == 0


def test_file_errors_counted_not_raised():
    wl = WorkloadTrace(capacity=16, trace_path="/nonexistent-dir/x.jsonl")
    assert _record(wl) is not None  # ring record survives the bad path
    assert wl.file_errors == 1


# ---------------------------------------------------------------------------
# garbage tolerance


def test_parse_rejects_garbage_without_raising():
    wl = WorkloadTrace(capacity=8, trace_path="")
    good = json.dumps(_record(wl), separators=(",", ":"))
    lines = [
        good,
        "",                                # blank: skipped, not rejected
        "{truncated",                      # crash mid-write
        json.dumps({"v": 999, "ts": 1.0}),  # future schema
        json.dumps({"not": "a record"}),
        json.dumps([1, 2, 3]),             # wrong shape entirely
        good.replace('"pt":32', '"pt":-1'),   # negative count
        good.replace('"pt":32', '"pt":true'),  # bool is not an int here
    ]
    records, rejected = parse_trace(lines)
    assert len(records) == 1 and rejected == 6


def test_parse_rejects_malformed_chain_and_ids():
    wl = WorkloadTrace(capacity=8, trace_path="", include_ids=True)
    good = json.dumps(_record(wl, ids=[1, 2]), separators=(",", ":"))
    bad_chain = good.replace('[[16,"aaaaaaaaaaaaaaaa"', '[[16,16')
    bad_ids = good.replace('"ids":[1,2]', '"ids":[1,"x"]')
    records, rejected = parse_trace([good, bad_chain, bad_ids])
    assert len(records) == 1 and rejected == 2


# ---------------------------------------------------------------------------
# seeded synthesis determinism


@pytest.mark.parametrize("kind", ["chat", "embed", "longctx", "agent"])
def test_synth_two_runs_byte_identical(kind):
    a = synth_trace(kind, 32, seed=7)
    b = synth_trace(kind, 32, seed=7)
    dump = lambda recs: "\n".join(  # noqa: E731
        json.dumps(r, separators=(",", ":")) for r in recs
    )
    assert dump(a) == dump(b)
    assert len(a) == 32
    # every synthetic record must survive its own parser
    records, rejected = parse_trace(dump(a).splitlines())
    assert len(records) == 32 and rejected == 0
    assert synth_trace(kind, 32, seed=8) != a  # the seed actually matters


def test_synth_agent_bursts_share_prefix_chains():
    recs = synth_trace("agent", 24, seed=3)
    heads = [r["chain"][0][1] for r in recs if r["chain"]]
    assert len(set(heads)) < len(heads)  # tool-call loops share a chain


def test_synth_unknown_kind_raises():
    with pytest.raises(ValueError):
        synth_trace("nope", 4)


def test_prompt_text_deterministic_and_prefix_sharing():
    recs = synth_trace("agent", 8, seed=5)
    assert prompt_text_for(recs[0]) == prompt_text_for(recs[0])
    # two records from the same burst share a chain head → shared textual
    # prefix (what keeps the replay's prefix-cache structure honest)
    same = [r for r in recs if r["chain"] and
            r["chain"][0][1] == recs[0]["chain"][0][1]]
    if len(same) >= 2:
        a, b = prompt_text_for(same[0]), prompt_text_for(same[1])
        shared = os.path.commonprefix([a, b])
        assert len(shared.split()) >= 1
        assert a != b  # rids differ → tails differ


# ---------------------------------------------------------------------------
# latency waterfall


def test_waterfall_exact_partition_coverage():
    wf = LatencyWaterfall(window=32)
    stages = {"admit_wait": 0.1, "prefill_queue": 0.2,
              "prefill_compute": 0.3, "decode": 0.4}
    wf.observe(stages, 1.0, rid="r1", ts=1.0)
    st = wf.stats()
    assert st["requests"] == 1
    assert st["coverage"] == 1.0
    assert st["stages"]["decode"]["p95_ms"] == pytest.approx(400.0)
    assert set(st["stage_s"]) == set(STAGES)


def test_waterfall_stage_seconds_accumulate_for_delta_bridge():
    wf = LatencyWaterfall(window=8)
    for _ in range(3):
        wf.observe({"decode": 0.5}, 0.5)
    assert wf.stage_seconds()["decode"] == pytest.approx(1.5)
    recent = wf.recent(2)
    assert len(recent) == 2 and recent[-1]["decode_ms"] == pytest.approx(500.0)


def test_waterfall_clamps_negative_stage_values():
    wf = LatencyWaterfall(window=8)
    wf.observe({"decode": -0.5, "stall": 0.25}, 0.25)
    assert wf.stage_seconds()["decode"] == 0.0
    assert wf.stats()["coverage"] == 1.0


def test_stall_threshold_knob(monkeypatch):
    monkeypatch.setenv("TPU_WATERFALL_STALL_MS", "100")
    assert workload.stall_threshold_s() == pytest.approx(0.1)
    monkeypatch.setenv("TPU_WATERFALL_STALL_MS", "junk")
    assert workload.stall_threshold_s() == pytest.approx(0.25)


def test_capture_is_thread_safe():
    wl = WorkloadTrace(capacity=4096, trace_path="")
    def worker(base):
        for i in range(200):
            _record(wl, base + i)
    threads = [threading.Thread(target=worker, args=(k * 1000,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert wl.stats()["records_total"] == 800


# ---------------------------------------------------------------------------
# purity pin (the dynamic half; the static half runs in test_analysis.py)


def test_workload_never_imports_executor(tmp_path):
    from llm_mcp_tpu.analysis.imports_lint import run_probe

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = run_probe("workload", repo, tmp=str(tmp_path))
    assert proc.returncode == 0, proc.stderr or proc.stdout


# ---------------------------------------------------------------------------
# e2e: a finished engine request produces the full ledger + capture record


def test_engine_waterfall_and_capture_e2e(monkeypatch, tmp_path):
    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine

    monkeypatch.setenv("TPU_WORKLOAD", "1")
    prior = workload.get_workload()
    cap = WorkloadTrace(capacity=64, trace_path="", include_ids=True)
    workload.set_workload(cap)
    try:
        eng = GenerationEngine(
            "tiny-llm", max_slots=2, max_seq_len=128,
            dtype=jnp.float32, decode_chunk=2,
        ).start()
        try:
            out = eng.generate("count me in", max_tokens=5, temperature=0.0)
            assert out["text"]
            ws = eng.waterfall_stats()
        finally:
            eng.shutdown()
    finally:
        workload.set_workload(prior)
    # acceptance: stages sum to within 5% of the measured request wall
    # (exact partition by construction — this is the 5%-criterion with
    # margin to spare)
    assert ws["requests"] >= 1
    assert ws["coverage"] == pytest.approx(1.0, abs=0.05)
    recs = cap.snapshot()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["v"] == SCHEMA_VERSION
    assert rec["model"] == "tiny-llm"
    assert rec["fin"] == "length" and rec["ot"] == 5
    assert rec["pt"] == len(rec["ids"])  # raw ids opted in via include_ids
    assert len(rec["chain"]) <= CHAIN_HEAD
    for n_tok, digest in rec["chain"]:
        assert n_tok > 0 and len(digest) == 16  # routing/prefix.py digests
    # the capture round-trips through its own parser
    parsed, rejected = parse_trace(
        [json.dumps(r, separators=(",", ":")) for r in recs]
    )
    assert parsed == recs and rejected == 0


def test_capture_replay_round_trip(monkeypatch, tmp_path):
    """A captured greedy trace, fed to a FRESH engine as each record's raw
    ids, reproduces the admitted-request count and token-identical text;
    the file round-trips with no rejected line and the second engine's
    waterfall holds the exact-partition invariant over what it served."""
    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.executor.engine import GenRequest

    def engine():
        return GenerationEngine(
            "tiny-llm", max_slots=2, max_seq_len=512, dtype=jnp.float32,
            decode_chunk=4,
        ).start()

    monkeypatch.setenv("TPU_WORKLOAD", "1")
    monkeypatch.setenv("TPU_WORKLOAD_IDS", "1")
    prior = workload.get_workload()
    cap = WorkloadTrace(capacity=64, trace_path="")  # ids: from the env
    workload.set_workload(cap)
    served: dict[str, str] = {}
    try:
        eng = engine()
        try:
            for i in range(3):
                out = eng.generate(
                    f"capture request {i}: one plain line about replay.",
                    max_tokens=5, temperature=0.0,
                )
                # a finished request's record is in the ring before its done
                # event publishes: the newest entry is this request's
                served[cap.snapshot(1)[0]["rid"]] = out["text"]
            assert eng.finished_requests == 3
        finally:
            eng.shutdown()
        path = tmp_path / "capture.jsonl"
        assert cap.dump(str(path)) == 3
    finally:
        workload.set_workload(prior)

    records, rejected = workload.load_trace(str(path))
    assert rejected == 0
    assert [r["rid"] for r in records] == list(served)
    assert all(r["ids"] and r["pt"] == len(r["ids"]) for r in records)

    replayed: dict[str, str] = {}
    eng = engine()
    try:
        for rec in records:
            req = eng.submit(GenRequest(
                prompt_ids=list(rec["ids"]), max_tokens=rec["mt"],
                temperature=rec["temp"], top_k=rec["top_k"], top_p=rec["top_p"],
            ))
            parts = []
            while isinstance(evt := req.out.get(timeout=120), dict):
                assert evt["type"] != "error", evt
                if evt["type"] == "token":
                    parts.append(evt["text"])
                elif evt["type"] == "done":
                    break
            replayed[rec["rid"]] = "".join(parts)
        assert eng.finished_requests == len(records)
        assert eng.total_errors == 0
        ws = eng.waterfall_stats()
    finally:
        eng.shutdown()
    assert replayed == served
    assert ws["requests"] == len(records)
    assert ws["coverage"] == pytest.approx(1.0, abs=0.05)
