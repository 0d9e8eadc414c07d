#!/usr/bin/env python3
"""Workload-trace toolbox: synthesize, summarize, digest, and replay.

A trace is the JSONL that telemetry/workload.py captures (one record per
finished admitted request: arrival wall-clock, prompt token count +
prefix-chain head hashes, sampling params, output tokens, finish reason)
or that ``--synth`` writes from the seeded generators.  This tool is the
operator's front door to the capture→replay loop:

    # synthesize a seeded trace to a file
    python scripts/replay.py --synth agent --n 64 --seed 7 --out agent.jsonl

    # validate + summarize a capture (rejected lines counted, not raised)
    python scripts/replay.py agent.jsonl

    # the seeded stream digest: two invocations with the same trace, seed
    # and compress print the same 16-hex sha — the determinism receipt
    python scripts/replay.py agent.jsonl --digest --seed 3 --compress 8

    # re-issue the trace open-loop against a live core with faithful
    # (compressed) inter-arrival gaps
    python scripts/replay.py agent.jsonl --core http://localhost:8080 \
        --compress 16 --model tiny-llm

Stdlib + the purity-pinned telemetry package only (urllib for --core), so
it runs anywhere the core does.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from llm_mcp_tpu.telemetry import workload  # noqa: E402


def load_source(src: str) -> tuple[list[dict], int]:
    """Trace records from a file path or a synth:<kind>:<n>[:seed] spec."""
    if src.startswith("synth:"):
        parts = src.split(":")
        kind = parts[1] if len(parts) > 1 else "chat"
        n = int(parts[2]) if len(parts) > 2 else 64
        seed = int(parts[3]) if len(parts) > 3 else 0
        return workload.synth_trace(kind, n, seed=seed), 0
    return workload.load_trace(src)


def stream_digest(records: list[dict], seed: int, compress: float) -> str:
    """Seeded 16-hex digest of the exact request stream a replay issues:
    the ONE statement of the replay plan. Gap (the capture's, divided by
    `compress`) + prompt (the record's raw `ids` where it has them, else
    `prompt_text_for`) + sampling params per record, keyed by (seed,
    compress) — byte-identical streams hash equal."""
    h = hashlib.sha256(f"seed={seed} compress={compress}".encode())
    prev_ts = None
    for rec in records:
        ts = float(rec["ts"])
        gap = 0.0 if prev_ts is None else max(0.0, ts - prev_ts) / max(1e-9, compress)
        prev_ts = ts
        prompt = rec["ids"] if rec.get("ids") else workload.prompt_text_for(rec)
        h.update(json.dumps(
            [round(gap, 9), prompt, rec.get("mt", 0), rec.get("temp", 0.0),
             rec.get("top_k", 0), rec.get("top_p", 1.0)],
            separators=(",", ":"),
        ).encode())
    return h.hexdigest()[:16]


def summarize(records: list[dict], rejected: int) -> dict:
    pts = sorted(r["pt"] for r in records) or [0]
    mts = sorted(r["mt"] for r in records) or [0]
    span = (records[-1]["ts"] - records[0]["ts"]) if len(records) > 1 else 0.0
    kinds = Counter(r["rid"][:2] for r in records)
    with_ids = sum(1 for r in records if r.get("ids"))
    chains = Counter(
        r["chain"][0][1] for r in records if r.get("chain")
    )
    shared = sum(c for c in chains.values() if c > 1)
    return {
        "records": len(records),
        "rejected_lines": rejected,
        "span_s": round(span, 3),
        "arrival_rps": round(len(records) / span, 3) if span > 0 else 0.0,
        "prompt_tokens": {"p50": pts[len(pts) // 2], "max": pts[-1]},
        "max_tokens": {"p50": mts[len(mts) // 2], "max": mts[-1]},
        "with_raw_ids": with_ids,
        "prefix_shared_requests": shared,
        "rid_prefixes": dict(kinds.most_common(8)),
    }


def replay_http(
    records: list[dict],
    core: str,
    model: str,
    compress: float,
    timeout: float,
) -> dict:
    """Open-loop HTTP replay: one POST per record, gaps honored globally."""
    results: list[dict] = []
    lock = threading.Lock()
    threads: list[threading.Thread] = []

    def issue(rec: dict, prompt: str) -> None:
        body = json.dumps({
            "model": model,
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max(1, rec.get("mt", 16)),
            "temperature": rec.get("temp", 0.0),
            "top_p": rec.get("top_p", 1.0),
        }).encode()
        t0 = time.perf_counter()
        try:
            r = urllib.request.Request(
                core.rstrip("/") + "/v1/chat/completions",
                data=body, headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(r, timeout=timeout) as resp:
                resp.read()
                ok, code = True, resp.status
        except urllib.error.HTTPError as e:
            ok, code = False, e.code
        except (urllib.error.URLError, OSError):
            ok, code = False, 0
        with lock:
            results.append({
                "rid": rec["rid"], "ok": ok, "code": code,
                "wall_ms": round((time.perf_counter() - t0) * 1e3, 1),
            })

    t_wall = time.perf_counter()
    prev_ts = None
    for rec in records:
        ts = float(rec["ts"])
        if prev_ts is not None:
            gap = max(0.0, ts - prev_ts) / max(1e-9, compress)
            if gap > 0:
                time.sleep(gap)
        prev_ts = ts
        prompt = workload.prompt_text_for(rec)
        th = threading.Thread(target=issue, args=(rec, prompt), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=timeout + 5.0)
    wall = time.perf_counter() - t_wall
    ok = sum(1 for r in results if r["ok"])
    walls = sorted(r["wall_ms"] for r in results) or [0.0]
    return {
        "issued": len(records),
        "completed": ok,
        "errors": len(results) - ok,
        "wall_s": round(wall, 3),
        "p50_request_ms": walls[len(walls) // 2],
        "p95_request_ms": walls[min(len(walls) - 1, int(0.95 * (len(walls) - 1)))],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", help="trace JSONL path or synth:<kind>:<n>[:seed]")
    ap.add_argument("--synth", metavar="KIND",
                    help="write a synthetic trace (chat/embed/longctx/agent) and exit")
    ap.add_argument("--n", type=int, default=64, help="synth record count")
    ap.add_argument("--seed", type=int, default=0, help="synth / stream seed")
    ap.add_argument("--out", help="output path for --synth")
    ap.add_argument("--digest", action="store_true",
                    help="print the seeded replay stream digest and exit")
    ap.add_argument("--compress", type=float, default=1.0,
                    help="time-compression factor for gaps (default 1)")
    ap.add_argument("--core", help="replay against this core URL over HTTP")
    ap.add_argument("--model", default="", help="model name for --core replay")
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-request timeout for --core replay (s)")
    args = ap.parse_args()

    if args.synth:
        if not args.out:
            ap.error("--synth requires --out")
        records = workload.synth_trace(args.synth, args.n, seed=args.seed)
        with open(args.out, "w", encoding="utf-8") as fh:
            for rec in records:
                fh.write(json.dumps(rec, separators=(",", ":")) + "\n")
        print(json.dumps({"synth": args.synth, "records": len(records),
                          "seed": args.seed, "out": args.out}))
        return 0

    if not args.trace:
        ap.error("a trace path (or synth:<kind>:<n> spec) is required")
    try:
        records, rejected = load_source(args.trace)
    except (OSError, ValueError) as e:
        print(f"replay: cannot load {args.trace}: {e}", file=sys.stderr)
        return 2
    if not records:
        print(f"replay: no valid records in {args.trace} "
              f"({rejected} rejected lines)", file=sys.stderr)
        return 2

    if args.digest:
        print(json.dumps({
            "stream_sha": stream_digest(records, args.seed, args.compress),
            "records": len(records), "seed": args.seed,
            "compress": args.compress,
        }))
        return 0

    if args.core:
        out = replay_http(records, args.core, args.model,
                          args.compress, args.timeout)
        out["compress"] = args.compress
        print(json.dumps(out, indent=2))
        return 0 if out["errors"] == 0 else 1

    print(json.dumps(summarize(records, rejected), indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
