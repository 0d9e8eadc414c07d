"""End-to-end request tracing: span library unit behavior, W3C traceparent
propagation over HTTP / job payloads / gRPC metadata, the /v1/traces API,
per-stage latency histograms, the slow-trace alert hook, and the
import-direction guarantee (telemetry never imports executor).

The e2e tests drive the REAL stack — HTTP server + in-process engine on the
CPU mesh — and assert the acceptance shape: one chat completion produces a
trace with nested http → route → engine.generate → engine.{admit,prefill,
decode} spans, TTFT and queue-wait attributes populated, and every stage of
llmtpu_stage_duration_seconds observed."""

import json
import re
import subprocess
import sys
import time

import httpx
import jax.numpy as jnp
import pytest

from llm_mcp_tpu.api.server import CoreServer
from llm_mcp_tpu.executor import GenerationEngine
from llm_mcp_tpu.state.db import Database
from llm_mcp_tpu.telemetry import tracing
from llm_mcp_tpu.utils.config import Config

# ---------------------------------------------------------------------------
# span library units
# ---------------------------------------------------------------------------


def test_traceparent_format_parse_roundtrip():
    tid, sid = "0af7651916cd43dd8448eb211c80319c", "b7ad6b7169203331"
    header = tracing.format_traceparent(tid, sid)
    assert header == f"00-{tid}-{sid}-01"
    assert tracing.parse_traceparent(header) == (tid, sid)


@pytest.mark.parametrize(
    "bad",
    [
        "",
        "garbage",
        "00-zzz-yyy-01",
        "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331",  # missing flags
        "00-" + "0" * 32 + "-b7ad6b7169203331-01",  # all-zero trace id
        "00-0af7651916cd43dd8448eb211c80319c-" + "0" * 16 + "-01",  # zero span
    ],
)
def test_malformed_traceparent_rejected(bad):
    assert tracing.parse_traceparent(bad) is None


def test_span_nesting_and_context_stack():
    tr = tracing.Tracer()
    with tr.span("outer") as outer:
        assert tracing.current_span() is outer
        with tr.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            assert tracing.current_traceparent() == inner.traceparent
        assert tracing.current_span() is outer
    assert tracing.current_span() is None
    spans = tr.get_trace(outer.trace_id)
    assert [s["name"] for s in spans] == ["outer", "inner"]
    root = next(s for s in spans if s["name"] == "outer")
    assert root["parent_id"] == ""


def test_remote_parent_joins_trace():
    """A traceparent string (the wire form) parents a span into the remote
    trace; a malformed one falls back to a fresh root trace."""
    tr = tracing.Tracer()
    with tr.span("origin") as origin:
        header = origin.traceparent
    with tr.span("joined", parent=header) as joined:
        assert joined.trace_id == origin.trace_id
        assert joined.parent_id == origin.span_id
    with tr.span("fresh", parent="not-a-traceparent") as fresh:
        assert fresh.trace_id != origin.trace_id
        assert fresh.parent_id == ""


def test_record_retroactive_span():
    tr = tracing.Tracer()
    with tr.span("root") as root:
        ctx = root.traceparent
    t0 = time.time() - 1.0
    sp = tr.record("queue.wait", t0, t0 + 0.5, parent=ctx, attrs={"job_id": "j1"})
    assert sp is not None
    got = next(s for s in tr.get_trace(root.trace_id) if s["name"] == "queue.wait")
    assert got["parent_id"] == root.span_id
    assert abs(got["duration_s"] - 0.5) < 1e-6
    assert got["attrs"]["job_id"] == "j1"
    # degenerate interval (end < start) records nothing
    assert tr.record("bogus", t0, t0 - 1.0, parent=ctx) is None


def test_ring_buffer_eviction_is_lru():
    tr = tracing.Tracer(max_traces=3)
    tids = []
    for i in range(5):
        with tr.span(f"r{i}") as sp:
            tids.append(sp.trace_id)
    assert tr.get_trace(tids[0]) == [] and tr.get_trace(tids[1]) == []
    for tid in tids[2:]:
        assert tr.get_trace(tid)
    assert len(tr.traces(limit=50)) == 3
    # newest-first summaries
    assert tr.traces(limit=1)[0]["trace_id"] == tids[-1]


def test_jsonl_export(tmp_path):
    path = str(tmp_path / "traces.jsonl")
    tr = tracing.Tracer(export_path=path)
    with tr.span("exported", attrs={"k": "v"}):
        pass
    lines = [json.loads(line) for line in open(path)]
    assert lines and lines[0]["name"] == "exported"
    assert lines[0]["attrs"]["k"] == "v"


def test_disabled_tracer_is_noop(monkeypatch):
    monkeypatch.setenv("TPU_TRACE", "0")
    tr = tracing.Tracer()
    assert not tr.enabled
    with tr.span("nope") as sp:
        assert sp.traceparent == ""
        assert tracing.current_span() is None  # noop spans never enter the stack
    assert tr.record("nope", time.time() - 1, time.time()) is None
    assert tr.traces(limit=50) == []


def test_observer_exceptions_are_swallowed():
    tr = tracing.Tracer()
    seen = []

    def bad(span):
        raise RuntimeError("observer bug")

    tr.add_observer(bad)
    tr.add_observer(lambda s: seen.append(s.name))
    with tr.span("survives"):
        pass
    assert seen == ["survives"]
    tr.remove_observer(bad)


def test_slow_trace_alert_hook(tmp_path):
    """Spans overrunning their deadline_s attribute surface as alerts on the
    next scan — the ISSUE's slow-trace hook (deadline comes from
    router.quality_deadline_s via the job's deadline_at)."""
    from llm_mcp_tpu.telemetry import AlertMonitor

    db = Database(":memory:")
    try:
        mon = AlertMonitor(db)
        tr = tracing.Tracer()
        mon.attach_tracer(tr)
        t0 = time.time() - 10.0
        tr.record("job", t0, t0 + 9.0, parent=tracing.NEW_TRACE,
                  attrs={"deadline_s": 2.0, "job_id": "j-slow"})
        tr.record("job", t0, t0 + 0.5, parent=tracing.NEW_TRACE,
                  attrs={"deadline_s": 2.0, "job_id": "j-fast"})
        alerts = mon.scan_once()
        slow = [a for a in alerts if "slow trace" in a]
        assert len(slow) == 1 and "9.0" in slow[0]
        # dedupe: the same trace does not re-alert
        assert not [a for a in mon.scan_once() if "slow trace" in a]
        mon.detach_tracer()
    finally:
        db.close()


def test_telemetry_never_imports_executor():
    """Import-direction lint: the telemetry package must stay dependency-free
    of the serving stack (executor/api/routing/worker/rpc) so every layer can
    import it without cycles or JAX weight."""
    code = (
        "import sys; import llm_mcp_tpu.telemetry; "
        "bad = [m for m in sys.modules if m.startswith(("
        "'llm_mcp_tpu.executor', 'llm_mcp_tpu.api', 'llm_mcp_tpu.routing', "
        "'llm_mcp_tpu.worker', 'llm_mcp_tpu.rpc', 'jax'))]; "
        "sys.exit('telemetry pulled in: %s' % bad if bad else 0)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr or proc.stdout


# ---------------------------------------------------------------------------
# e2e: real server + engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
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


def _get_trace(base: str, trace_id: str, want_names: set[str], timeout=10.0) -> list[dict]:
    """Fetch a trace, waiting briefly for spans recorded on other threads
    (the engine loop records decode just before the response unblocks)."""
    deadline = time.monotonic() + timeout
    spans: list[dict] = []
    while time.monotonic() < deadline:
        r = httpx.get(f"{base}/v1/traces/{trace_id}")
        if r.status_code == 200:
            spans = r.json()["spans"]
            if want_names.issubset({s["name"] for s in spans}):
                return spans
        time.sleep(0.05)
    return spans


def test_chat_completion_trace_e2e(base):
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={
            "model": "tiny-llm",
            "messages": [{"role": "user", "content": "trace me"}],
            "max_tokens": 6,
            "temperature": 0,
        },
        timeout=120.0,
    )
    assert r.status_code == 200
    tid = r.headers.get("x-trace-id")
    assert tid, "traced responses must carry X-Trace-Id"

    want = {
        "http POST /v1/chat/completions", "route", "engine.generate",
        "engine.admit", "engine.prefill", "engine.decode",
    }
    spans = _get_trace(base, tid, want)
    by_name = {s["name"]: s for s in spans}
    assert want.issubset(by_name), sorted(by_name)
    assert len(spans) >= 4

    # nesting: http is the root; route and engine.generate are its children;
    # the engine phases parent under engine.generate (via req.trace_ctx)
    http = by_name["http POST /v1/chat/completions"]
    assert http["parent_id"] == ""
    assert by_name["route"]["parent_id"] == http["span_id"]
    gen = by_name["engine.generate"]
    assert gen["parent_id"] == http["span_id"]
    for phase in ("engine.admit", "engine.prefill", "engine.decode"):
        assert by_name[phase]["parent_id"] == gen["span_id"], phase

    # attribute contracts
    assert by_name["route"]["attrs"]["reason"] == "local-engine"
    assert float(by_name["engine.prefill"]["attrs"]["ttft_ms"]) > 0
    assert by_name["engine.decode"]["attrs"]["completion_tokens"] == 6
    assert http["attrs"]["http.status"] == 200


def test_traces_listing(base):
    r = httpx.get(f"{base}/v1/traces?limit=5")
    assert r.status_code == 200
    body = r.json()
    assert body["enabled"] is True
    assert body["traces"], "the chat trace above must be listed"
    summary = body["traces"][0]
    assert {"trace_id", "name", "start", "duration_s", "spans", "status"} <= set(summary)


def test_trace_not_found_is_404(base):
    assert httpx.get(f"{base}/v1/traces/{'f' * 32}").status_code == 404


def test_job_trace_has_queue_wait_span(base):
    """submit → claim → complete over the HTTP worker protocol: the submit
    trace gains a queue.wait span (submit→claim, parented under the submit
    request) and a job span carrying the terminal status."""
    jid = httpx.post(
        f"{base}/v1/jobs", json={"kind": "echo", "payload": {"data": 1}}
    ).json()["job_id"]
    tid = None
    job = httpx.get(f"{base}/v1/jobs/{jid}").json()
    ctx = job["payload"].get("_traceparent")
    assert ctx, "submit must stamp the trace context into the payload"
    tid = tracing.parse_traceparent(ctx)[0]

    time.sleep(0.05)  # a measurable queue wait
    claimed = httpx.post(
        f"{base}/v1/jobs/claim", json={"worker_id": "w-trace", "kinds": ["echo"]}
    ).json()["job"]
    assert claimed["id"] == jid
    httpx.post(
        f"{base}/v1/jobs/{jid}/complete",
        json={"worker_id": "w-trace", "result": {"ok": True}},
    )

    spans = _get_trace(base, tid, {"queue.wait", "job"})
    by_name = {s["name"]: s for s in spans}
    assert "queue.wait" in by_name and "job" in by_name, sorted(by_name)
    qw = by_name["queue.wait"]
    assert qw["attrs"]["worker_id"] == "w-trace"
    assert qw["duration_s"] > 0
    # queue.wait parents under the submitting request's http span
    http = next(s for s in spans if s["name"].startswith("http POST /v1/jobs"))
    assert qw["parent_id"] == http["span_id"]
    assert by_name["job"]["attrs"]["job.status"] == "done"


def test_grpc_metadata_propagation(server, base):
    """The gRPC transport joins the same traces: client invocation metadata
    carries the traceparent, the server wraps worker-protocol RPCs in rpc.*
    spans, and queue-wait/job spans record across the process boundary."""
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from llm_mcp_tpu.rpc import GrpcCoreClient, GrpcCoreServer
    from llm_mcp_tpu.state.catalog import Catalog
    from llm_mcp_tpu.state.queue import JobQueue

    db = Database(":memory:")
    queue = JobQueue(db)
    srv = GrpcCoreServer(queue, Catalog(db)).start("127.0.0.1:0")
    client = GrpcCoreClient(f"127.0.0.1:{srv.port}", timeout_s=10.0)
    tr = tracing.get_tracer()
    try:
        with tr.span("test.grpc-root") as root:
            job = client.submit("echo", {"data": 2})
            tid = root.trace_id
        assert job["payload"]["_traceparent"]
        claimed = client.claim("w-grpc")
        assert claimed["id"] == job["id"]
        with tr.span("worker.execute", parent=job["payload"]["_traceparent"]):
            client.complete(job["id"], "w-grpc", {"ok": True})

        spans = _get_trace(base, tid, {"rpc.SubmitJob", "queue.wait", "rpc.CompleteJob"})
        by_name = {s["name"]: s for s in spans}
        assert {"rpc.SubmitJob", "queue.wait", "job", "rpc.CompleteJob"} <= set(by_name)
        # nesting across the wire: submit RPC under the client's root span,
        # queue.wait under the submit RPC (payload-propagated context)
        assert by_name["rpc.SubmitJob"]["parent_id"] == root.span_id
        assert by_name["queue.wait"]["parent_id"] == by_name["rpc.SubmitJob"]["span_id"]
        assert by_name["rpc.CompleteJob"]["parent_id"] == by_name["worker.execute"]["span_id"]
    finally:
        client.close()
        srv.stop(0)
        db.close()


def test_stage_histogram_observes_every_stage(base, server):
    """After the flows above, llmtpu_stage_duration_seconds has counted
    every stage: queue_wait, route, rpc, prefill, decode."""
    stages = ("queue_wait", "route", "rpc", "prefill", "decode")
    text = httpx.get(f"{base}/metrics").text
    if not all(f'llmtpu_stage_duration_seconds_count{{stage="{st}"}}' in text for st in stages):
        # `--dist load` deals a file's tests to any worker: the flows above
        # may have run against another process's server, so run them here
        test_chat_completion_trace_e2e(base)
        test_job_trace_has_queue_wait_span(base)
        test_grpc_metadata_propagation(server, base)
        text = httpx.get(f"{base}/metrics").text
    for stage in stages:
        m = re.search(
            rf'llmtpu_stage_duration_seconds_count{{stage="{stage}"}} (\d+\.?\d*)', text
        )
        assert m, f"stage {stage} missing from /metrics"
        assert float(m.group(1)) >= 1.0, f"stage {stage} never observed"


def test_disabled_tracing_changes_nothing(base, server, monkeypatch):
    """TPU_TRACE=0 (flipped live): endpoints behave identically but no spans
    are recorded and no X-Trace-Id is attached."""
    monkeypatch.setenv("TPU_TRACE", "0")
    before = len(server.tracer.traces(limit=512))
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={
            "model": "tiny-llm",
            "messages": [{"role": "user", "content": "untraced"}],
            "max_tokens": 4,
            "temperature": 0,
        },
        timeout=120.0,
    )
    assert r.status_code == 200
    assert r.json()["choices"][0]["message"]["content"] is not None
    assert "x-trace-id" not in r.headers
    jid = httpx.post(f"{base}/v1/jobs", json={"kind": "echo"}).json()["job_id"]
    job = httpx.get(f"{base}/v1/jobs/{jid}").json()
    assert "_traceparent" not in job["payload"]
    assert len(server.tracer.traces(limit=512)) == before
    body = httpx.get(f"{base}/v1/traces").json()
    assert body["enabled"] is False
