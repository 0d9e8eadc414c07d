#!/usr/bin/env python3
"""The load generator: a child process that talks only HTTP to 127.0.0.1.

    python3 benchmark/loadgen.py <plan.json> <records.json>

It never imports JAX (the parent holds the chip) and imports nothing of the
program. The plan is what `trafficgen.make_plan` gives, plus `port`, `t_start`
(an absolute CLOCK_MONOTONIC reading, which parent and child share), `stop_s`
(closed loops issue no new request after it) and `timeout_s`. It writes one
record per request it sent, every time in seconds from `t_start`:

    chat:       i, due, sent, status, events (times of content deltas), done,
                finish, completion_tokens, prompt_tokens, trace, error
    embeddings: i, sent, status, done, inputs, bad (vectors not finite, not of
                unit norm or not `dimensions` wide), error

One process, a thread for each closed-loop client or open-loop worker; the code is the benchmark's own.
"""

from __future__ import annotations

import http.client
import json
import math
import queue
import sys
import threading
import time

try:  # started as a script, its own directory is on the path
    import trafficgen
except ImportError:  # imported as benchmark.loadgen (tests)
    from benchmark import trafficgen

OPEN_WORKERS = 96  # open loop: requests in flight at once before one runs late


def trace_id(seed: int, i: int) -> str:
    """32 hex digits the server's waterfall rows carry back (W3C traceparent)."""
    return f"be{seed & 0xFFFFFFFFFF:010x}{i:020x}"


def chat_once(plan: dict, req: dict, now) -> dict:
    """One streaming chat. Times come from `now()`, seconds from t_start."""
    seed, i = plan["seed"], req["i"]
    tid = trace_id(seed, i)
    body = json.dumps({
        "model": plan["model"], "stream": True, "max_tokens": req["max_tokens"],
        "temperature": plan["temperature"],
        "messages": [{"role": "user",
                      "content": trafficgen.text(*req["prompt"][0], f"{plan['salt']}{i}")}],
    })
    rec: dict = {"i": i, "due": req.get("due"), "trace": tid, "events": [],
                 "status": 0, "done": None, "finish": None,
                 "completion_tokens": 0, "prompt_tokens": 0, "error": ""}
    conn = http.client.HTTPConnection("127.0.0.1", plan["port"], timeout=plan["timeout_s"])
    try:
        rec["sent"] = now()
        conn.request("POST", "/v1/chat/completions", body, {
            "Content-Type": "application/json",
            "traceparent": f"00-{tid}-{i + 1:016x}-01"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read()[:200].decode("utf-8", "replace")
            return rec
        events = rec["events"]
        for raw in resp:
            if not raw.startswith(b"data:"):
                continue
            t = now()
            data = raw[5:].strip()
            if data == b"[DONE]":
                rec["done"] = t
                break
            evt = json.loads(data)
            if "error" in evt:
                rec["error"] = str(evt["error"])[:200]
                break
            choice = evt["choices"][0]
            if choice["delta"].get("content") is not None:
                events.append(round(t, 6))
            if choice.get("finish_reason"):
                rec["finish"] = choice["finish_reason"]
            usage = evt.get("usage")
            if usage:
                rec["completion_tokens"] = int(usage.get("completion_tokens", 0))
                rec["prompt_tokens"] = int(usage.get("prompt_tokens", 0))
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        conn.close()
    return rec


def embed_once(plan: dict, req: dict, now) -> dict:
    """One POST /v1/embeddings; every vector is checked here."""
    i = req["i"]
    texts = [trafficgen.text(n, rank, f"{plan['salt']}{i}k{k}")
             for k, (n, rank) in enumerate(req["prompt"])]
    body: dict = {"model": plan["model"], "input": texts}
    if plan["dimensions"]:
        body["dimensions"] = plan["dimensions"]
    rec: dict = {"i": i, "due": req.get("due"), "status": 0, "done": None,
                 "inputs": len(texts), "bad": 0, "error": ""}
    conn = http.client.HTTPConnection("127.0.0.1", plan["port"], timeout=plan["timeout_s"])
    try:
        rec["sent"] = now()
        conn.request("POST", "/v1/embeddings", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        rec["done"] = now()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = raw[:200].decode("utf-8", "replace")
            return rec
        data = json.loads(raw)["data"]
        want = plan["dimensions"]
        bad = abs(len(data) - len(texts))
        for row in data:
            vec = row["embedding"]
            norm = math.sqrt(math.fsum(x * x for x in vec))
            if (want and len(vec) != want) or not math.isfinite(norm) or abs(norm - 1.0) > 1e-2:
                bad += 1
        rec["bad"] = bad
    except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        conn.close()
    return rec


def run(plan: dict) -> list[dict]:
    t_start = float(plan["t_start"])

    def now() -> float:
        return time.monotonic() - t_start

    once = chat_once if plan["endpoint"] == "chat" else embed_once
    reqs = plan["requests"]
    records: list[dict] = []
    lock = threading.Lock()

    def do(req: dict) -> None:
        rec = once(plan, req, now)
        with lock:
            records.append(rec)

    if plan["loop"] == "open":
        q: queue.Queue = queue.Queue()

        def worker() -> None:
            while (req := q.get()) is not None:
                do(req)

        threads = [threading.Thread(target=worker, daemon=True) for _ in range(OPEN_WORKERS)]
        for t in threads:
            t.start()
        for req in reqs:
            wait = req["due"] - now()
            if wait > 0:
                time.sleep(wait)
            q.put(req)
        for _ in threads:
            q.put(None)
    else:
        nxt = iter(reqs)
        stop_s = float(plan["stop_s"])

        def client() -> None:
            while now() < stop_s:
                with lock:
                    req = next(nxt, None)
                if req is None:
                    return
                do(req)

        threads = [threading.Thread(target=client, daemon=True) for _ in range(plan["clients"])]
        while now() < 0:  # everybody starts at t_start
            time.sleep(min(0.05, -now()))
        for t in threads:
            t.start()
    deadline = time.monotonic() + float(plan["timeout_s"]) + float(plan["stop_s"]) + 30.0
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    with lock:
        return sorted(records, key=lambda r: r["i"])


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    records = run(plan)
    with open(out_path, "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
