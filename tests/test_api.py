"""Core API integration tests: real HTTP server + in-process TPU engines on
the CPU mesh. The reference has no such tests (SURVEY §4: unit-only); this
is the fake-backend-free integration layer it lacks."""

import json
import threading
import time

import httpx
import jax.numpy as jnp
import pytest

from llm_mcp_tpu.api.server import CoreServer
from llm_mcp_tpu.executor import EmbeddingEngine, GenerationEngine
from llm_mcp_tpu.state.db import Database
from llm_mcp_tpu.utils.config import Config


@pytest.fixture(scope="module")
def server():
    cfg = Config()
    cfg.db_path = ":memory:"
    gen = GenerationEngine(
        "tiny-llm", max_slots=4, max_seq_len=128, dtype=jnp.float32
    ).start()
    emb = EmbeddingEngine("tiny-embed", max_batch=4, max_seq_len=64, dtype=jnp.float32)
    srv = CoreServer(
        cfg,
        db=Database(":memory:"),
        gen_engines={"tiny-llm": gen},
        embed_engines={"tiny-embed": emb},
    ).start("127.0.0.1", 0)
    yield srv
    srv.shutdown()


@pytest.fixture(scope="module")
def base(server):
    return f"http://127.0.0.1:{server.api.port}"


def test_health(base):
    r = httpx.get(f"{base}/health")
    assert r.status_code == 200
    assert r.json()["status"] == "ok"


def test_metrics_exposition(base):
    r = httpx.get(f"{base}/metrics")
    assert r.status_code == 200
    assert b"llmcore_chat_requests_total" in r.content or b"# HELP" in r.content


def test_not_found_and_method_not_allowed(base):
    assert httpx.get(f"{base}/nope").status_code == 404
    assert httpx.get(f"{base}/v1/chat/completions").status_code == 405


def test_job_lifecycle(base):
    r = httpx.post(f"{base}/v1/jobs", json={"kind": "echo", "payload": {"x": 1}})
    assert r.status_code == 202
    jid = r.json()["job_id"]

    r = httpx.get(f"{base}/v1/jobs/{jid}")
    assert r.json()["status"] == "queued"

    r = httpx.post(f"{base}/v1/jobs/claim", json={"worker_id": "w1", "kinds": ["echo"]})
    job = r.json()["job"]
    assert job["id"] == jid

    r = httpx.post(f"{base}/v1/jobs/{jid}/heartbeat", json={"worker_id": "w1"})
    assert r.json()["status"] == "ok"

    r = httpx.post(
        f"{base}/v1/jobs/{jid}/complete",
        json={"worker_id": "w1", "result": {"echo": {"x": 1}}},
    )
    assert r.json()["status"] == "done"

    r = httpx.get(f"{base}/v1/jobs/{jid}")
    body = r.json()
    assert body["status"] == "done"
    assert body["result"] == {"echo": {"x": 1}}


def test_job_fail_requeues_then_errors(base):
    jid = httpx.post(
        f"{base}/v1/jobs", json={"kind": "flaky", "max_attempts": 2}
    ).json()["job_id"]
    for attempt in (1, 2):
        job = httpx.post(
            f"{base}/v1/jobs/claim", json={"worker_id": "w2", "kinds": ["flaky"]}
        ).json()["job"]
        assert job["id"] == jid and job["attempts"] == attempt
        r = httpx.post(
            f"{base}/v1/jobs/{jid}/fail", json={"worker_id": "w2", "error": "boom"}
        )
        expected = "queued" if attempt == 1 else "error"
        assert r.json()["status"] == expected
    assert httpx.get(f"{base}/v1/jobs/{jid}").json()["status"] == "error"


def test_job_wrong_worker_conflict(base):
    jid = httpx.post(f"{base}/v1/jobs", json={"kind": "solo"}).json()["job_id"]
    httpx.post(f"{base}/v1/jobs/claim", json={"worker_id": "wa", "kinds": ["solo"]})
    r = httpx.post(
        f"{base}/v1/jobs/{jid}/complete", json={"worker_id": "IMPOSTOR", "result": {}}
    )
    assert r.status_code == 409


def test_job_sse_stream(base):
    jid = httpx.post(f"{base}/v1/jobs", json={"kind": "sse-test"}).json()["job_id"]
    events = []

    def consume():
        with httpx.stream("GET", f"{base}/v1/jobs/{jid}/stream", timeout=30.0) as r:
            for line in r.iter_lines():
                if line.startswith("data: "):
                    events.append(json.loads(line[6:]))
                if line.startswith("event: end"):
                    break

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.3)
    httpx.post(f"{base}/v1/jobs/claim", json={"worker_id": "w3", "kinds": ["sse-test"]})
    httpx.post(f"{base}/v1/jobs/{jid}/complete", json={"worker_id": "w3", "result": {}})
    t.join(timeout=20)
    assert not t.is_alive()
    statuses = [e["status"] for e in events if "status" in e]
    assert statuses[0] == "queued"
    assert "done" in statuses


def test_chat_completions_sync(base):
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={
            "model": "tiny-llm",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 8,
            "temperature": 0,
        },
        timeout=120.0,
    )
    assert r.status_code == 200
    body = r.json()
    assert body["object"] == "chat.completion"
    assert body["choices"][0]["message"]["role"] == "assistant"
    assert body["usage"]["completion_tokens"] <= 8
    assert body["choices"][0]["finish_reason"] in ("stop", "length")


def test_chat_completions_stream_sse(base):
    chunks = []
    with httpx.stream(
        "POST",
        f"{base}/v1/chat/completions",
        json={
            "model": "tiny-llm",
            "messages": [{"role": "user", "content": "stream please"}],
            "max_tokens": 6,
            "temperature": 0,
            "stream": True,
        },
        timeout=120.0,
    ) as r:
        assert r.status_code == 200
        assert r.headers["content-type"].startswith("text/event-stream")
        for line in r.iter_lines():
            if line.startswith("data: "):
                chunks.append(line[6:])
    assert chunks[-1] == "[DONE]"
    parsed = [json.loads(c) for c in chunks[:-1]]
    assert parsed[0]["choices"][0]["delta"].get("role") == "assistant"
    assert parsed[0]["object"] == "chat.completion.chunk"
    finals = [p for p in parsed if p["choices"][0]["finish_reason"]]
    assert finals and "usage" in finals[-1]


def test_chat_validation_errors(base):
    r = httpx.post(f"{base}/v1/chat/completions", json={"model": "tiny-llm"})
    assert r.status_code == 400  # messages required
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={"model": "tiny-llm", "messages": [{"role": "user", "content": "x"}], "max_tokens": 0},
    )
    assert r.status_code == 400  # max_tokens >= 1
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={"model": "no-such-model", "messages": [{"role": "user", "content": "x"}]},
    )
    assert r.status_code == 503


def test_embeddings_single_and_batch(base):
    r = httpx.post(
        f"{base}/v1/embeddings",
        json={"model": "tiny-embed", "input": "hello"},
        timeout=60.0,
    )
    assert r.status_code == 200
    body = r.json()
    assert body["object"] == "list"
    assert len(body["data"]) == 1
    assert body["data"][0]["object"] == "embedding"
    assert body["usage"]["prompt_tokens"] > 0

    r = httpx.post(
        f"{base}/v1/embeddings",
        json={"model": "tiny-embed", "input": ["a", "b", "c"], "dimensions": 16},
        timeout=60.0,
    )
    body = r.json()
    assert [d["index"] for d in body["data"]] == [0, 1, 2]
    assert all(len(d["embedding"]) == 16 for d in body["data"])


def test_embedding_counters_reach_an_operator(base, server):
    """EmbeddingEngine.stats() is read by engines_info: /v1/debug/health and
    the dashboard show the forward counters, never the `recent` window."""
    emb = server.embed_engines["tiny-embed"]
    before = emb.stats(recent=False)
    r = httpx.post(f"{base}/v1/embeddings", timeout=60.0,
                   json={"model": "tiny-embed", "input": ["x" * 9, "y" * 30, "z"]})
    assert r.status_code == 200
    for url, path in ((f"{base}/v1/debug/health", ("checks", "engines")),
                      (f"{base}/v1/dashboard", ("engines",))):
        doc = httpx.get(url).json()
        for k in path:
            doc = doc[k]
        blk = doc["tiny-embed"]
        assert "recent" not in blk and blk["kind"] == "embed"
        assert blk["forwards"] == before["forwards"] + 1
        assert blk["rows"] == before["rows"] + 3
        assert blk["rows_padded"] == before["rows_padded"] + 4
        assert blk["padded_tokens"] > blk["true_tokens"] > before["true_tokens"]
        # PR 53: seconds blocked in the fetch, seconds under the lock (staging and
        # dispatch), and how often a forward was dispatched behind an unready one
        assert blk["forward_s"] > before["forward_s"]
        assert blk["host_locked_s"] > before["host_locked_s"] and blk["lock_wait_s"] >= 0
        assert before["ahead"] <= blk["ahead"] <= blk["forwards"] - 1 and blk["inflight_max"] >= 1
        # an encoder keeps one text a row: packing never engages
        assert blk["rows_packed"] == blk["rows"] and blk["texts_per_row"] == 1.0
        assert blk["pad_waste_pct"] == pytest.approx(
            100.0 * (1.0 - blk["true_tokens"] / blk["padded_tokens"]))


def test_packing_is_visible_to_an_operator(base, server):
    """PR 31: a decoder-architecture embedder packs a request's texts into
    rows; /v1/debug/health shows texts a row and the padded share, and the
    answers come back in the request's order."""
    emb = EmbeddingEngine("tiny-qwen3", max_seq_len=128, dtype=jnp.float32)
    server.embed_engines["tiny-qwen3"] = emb
    try:
        texts = ["p" * 50, "q" * 9, "r" * 30, "s" * 20, "t" * 70, "u" * 40]
        r = httpx.post(f"{base}/v1/embeddings", timeout=120.0,
                       json={"model": "tiny-qwen3", "input": texts})
        assert r.status_code == 200
        served = [d["embedding"] for d in r.json()["data"]]
        for i in (1, 4):
            alone, _ = emb.embed([texts[i]])
            assert max(abs(a - b) for a, b in zip(served[i], alone[0])) < 1e-4
        blk = httpx.get(f"{base}/v1/debug/health").json()["checks"]["engines"]["tiny-qwen3"]
        # 225 tokens in two rows of 128 (the sum's bucket is 256, the cap on a row 128), then two single texts
        assert (blk["rows"], blk["rows_packed"], blk["rows_padded"]) == (8, 4, 4)
        assert blk["texts_per_row"] == 2.0
        assert 0.0 < blk["pad_waste_pct"] < 50.0
    finally:
        del server.embed_engines["tiny-qwen3"]


def test_embeddings_validation(base):
    assert httpx.post(f"{base}/v1/embeddings", json={"input": 42}).status_code == 400
    assert httpx.post(f"{base}/v1/embeddings", json={"input": []}).status_code == 400


def test_llm_request_routes_and_queues(base):
    r = httpx.post(
        f"{base}/v1/llm/request",
        json={"kind": "generate", "prompt": "hi", "quality": "turbo"},
    )
    assert r.status_code == 202
    body = r.json()
    assert body["provider"] == "tpu"
    assert body["model"] == "tiny-llm"
    job = httpx.get(f"{base}/v1/jobs/{body['job_id']}").json()
    assert job["status"] == "queued"
    assert job["payload"]["_tier"]
    assert job["deadline_at"] is not None


def test_models_devices_benchmarks(base):
    models = httpx.get(f"{base}/v1/models").json()["models"]
    assert {m["id"] for m in models} >= {"tiny-llm", "tiny-embed"}
    devices = httpx.get(f"{base}/v1/devices").json()["devices"]
    local = [d for d in devices if d["id"] == "tpu-local"]
    assert local and "tiny-llm" in local[0]["models"]
    assert httpx.get(f"{base}/v1/benchmarks").status_code == 200


def test_dashboard_and_debug(base):
    dash = httpx.get(f"{base}/v1/dashboard").json()
    assert dash["devices_online"] >= 1
    assert "jobs" in dash and "issues" in dash
    assert any(h["role"] for h in dash["hosts"])
    # serve-budget breakdown per engine (cumulative)
    gen_info = next(
        v for v in dash["engines"].values() if v["kind"] == "generate"
    )
    assert set(gen_info["phase_s"]) == {
        "dispatch", "fetch", "admit", "prefill", "emit", "idle",
    }

    health = httpx.get(f"{base}/v1/debug/health").json()
    assert health["status"] == "ok"
    assert health["checks"]["db"]["ok"]

    cap = httpx.get(f"{base}/v1/debug/capacity").json()
    assert cap["total_slots"] >= 4  # tiny-llm engine has 4 slots

    smoke = httpx.post(f"{base}/v1/debug/test").json()
    assert smoke["status"] == "ok"
    assert smoke["results"]["queue_roundtrip"]["ok"]

    actions = httpx.get(f"{base}/v1/debug/actions").json()["actions"]
    assert any(a["path"] == "/v1/chat/completions" for a in actions)


def test_feedback_and_stats(base):
    r = httpx.post(f"{base}/v1/feedback", json={"model": "tiny-llm", "rating": "up"})
    assert r.json()["status"] == "ok"
    stats = httpx.get(f"{base}/v1/models/stats").json()["stats"]
    row = [s for s in stats if s["model_id"] == "tiny-llm"]
    assert row and row[0]["feedback_up"] >= 1


def test_costs_summary(base):
    r = httpx.get(f"{base}/v1/costs/summary")
    assert r.status_code == 200
    assert "costs" in r.json()


def test_devices_offline_requeues(base, server):
    server.catalog.upsert_device("tpu-remote", addr="10.9.9.9:8080")
    jid = httpx.post(
        f"{base}/v1/jobs",
        json={"kind": "pinned", "payload": {"device_id": "tpu-remote"}},
    ).json()["job_id"]
    httpx.post(f"{base}/v1/jobs/claim", json={"worker_id": "w9", "kinds": ["pinned"]})
    r = httpx.post(f"{base}/v1/devices/offline", json={"device_ids": ["tpu-remote"]})
    assert r.json()["requeued_jobs"] == 1
    # lease reset → immediately reclaimable by another worker
    job = httpx.post(
        f"{base}/v1/jobs/claim", json={"worker_id": "w10", "kinds": ["pinned"]}
    ).json()["job"]
    assert job and job["id"] == jid


def test_smart_model_selection_empty_model(base, server):
    server.catalog.set_ranking("tiny-llm", "chat", 9.5)
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={"messages": [{"role": "user", "content": "pick for me"}], "max_tokens": 4},
        timeout=120.0,
    )
    assert r.status_code == 200
    assert r.json()["model"] == "tiny-llm"
    assert r.headers.get("X-Selected-Model") == "tiny-llm"


def test_smart_selection_accuracy_weighting(base, server):
    """Reference scoring (`handlers.go:3040-3144`): category score × accuracy
    weight − cost factor × log10 price tier; low accuracy prefers the cheap
    model, critical accuracy ignores price entirely. Context-unfit models are
    skipped. Headers override body fields."""
    cat = server.catalog
    try:
        _smart_selection_accuracy_body(base, cat)
    finally:
        # module-scoped server: don't leak rankings into later tests
        for mid in ("premium-llm", "tiny-ctx"):
            cat.db.execute("DELETE FROM model_rankings WHERE model_id = ?", (mid,))
            cat.db.execute("DELETE FROM model_pricing WHERE model_id = ?", (mid,))
            cat.db.execute("DELETE FROM models WHERE id = ?", (mid,))
        cat.db.execute(
            "DELETE FROM model_rankings WHERE model_id='tiny-llm' AND category='code'"
        )
        cat.db.execute("DELETE FROM model_pricing WHERE model_id='tiny-llm'")


def _smart_selection_accuracy_body(base, cat):
    # an expensive high-scorer and a cheap mid-scorer, both rankable
    cat.set_ranking("tiny-llm", "code", 60.0)
    cat.set_pricing("tiny-llm", 0.05, 0.1)  # cheap
    cat.upsert_model("premium-llm", name="premium", kind="llm", context_k=128)
    cat.set_ranking("premium-llm", "code", 90.0)
    cat.set_pricing("premium-llm", 15.0, 60.0)  # log10(15000+1)*10 ≈ 42 tier

    def pick(**kw):
        r = httpx.post(
            f"{base}/v1/chat/completions",
            json={
                "messages": [{"role": "user", "content": "write code"}],
                "max_tokens": 4,
                **kw.pop("body", {}),
            },
            timeout=120.0,
            **kw,
        )
        return r.headers.get("X-Selected-Model")

    # low accuracy: 60*0.3 − 3*~2.4(tier) ≈ 10.8 beats 90*0.3 − 3*42 ≈ −99
    assert pick(body={"task_type": "code", "accuracy": "low"}) == "tiny-llm"
    # critical accuracy: price ignored → the 90-scorer wins
    assert pick(body={"task_type": "code", "accuracy": "critical"}) == "premium-llm"
    # headers override body (handlers.go:2124-2144)
    assert (
        pick(
            body={"task_type": "code", "accuracy": "critical"},
            headers={"X-Accuracy": "low"},
        )
        == "tiny-llm"
    )
    # cost cap excludes the expensive model even at critical accuracy:
    # pricey's output side alone (4 tok × $60/M ≈ 2.4e-4) busts a 1e-5 cap
    # that tiny-llm (≈9e-7) passes
    assert (
        pick(body={"task_type": "code", "accuracy": "critical",
                   "max_cost_usd": 0.00001})
        == "tiny-llm"
    )
    # every ranked model over the cap → 503, NOT a silent fallback model
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={"messages": [{"role": "user", "content": "write code"}],
              "max_tokens": 4, "task_type": "code", "accuracy": "critical",
              "max_cost_usd": 1e-9},
        timeout=120.0,
    )
    assert r.status_code == 503, r.text
    assert "X-Selected-Model" not in r.headers
    # same when every ranked model fails CONTEXT fit (reference behavior:
    # "no suitable model found", handlers.go:3130) — tiny-llm's 8k context
    # can't hold a ~12.5k-token prompt, premium-llm is shrunk below it too
    cat.upsert_model("premium-llm", context_k=1)
    cat.upsert_model("tiny-llm", context_k=1)
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={"messages": [{"role": "user", "content": "y" * 50_000}],
              "max_tokens": 4, "task_type": "code"},
        timeout=120.0,
    )
    cat.upsert_model("tiny-llm", context_k=8)  # restore both
    cat.upsert_model("premium-llm", context_k=128)
    assert r.status_code == 503, r.text
    # context fit: a model whose context can't hold the prompt is skipped
    cat.upsert_model("tiny-ctx", name="tiny-ctx", kind="llm", context_k=1)
    cat.set_ranking("tiny-ctx", "code", 99.0)
    long_prompt = "x" * 5000  # ≈1250 tokens > 1k context
    r = httpx.post(
        f"{base}/v1/chat/completions",
        json={
            "messages": [{"role": "user", "content": long_prompt}],
            "max_tokens": 4,
            "task_type": "code",
            "accuracy": "critical",
        },
        timeout=120.0,
    )
    assert r.headers.get("X-Selected-Model") == "premium-llm"
