"""Workload capture, trace synthesis, and the per-request latency waterfall.

The perf observatory (perf.py) explains the *steady state* and the flight
recorder (recorder.py) journals the *anomaly*, but both describe traffic
someone else made up: every line of record comes from synthetic
closed-loop clients.  This module closes that gap in three pieces:

1. **Workload capture** — every admitted request that finishes becomes one
   compact schema-versioned JSONL record: arrival wall-clock, prompt token
   count plus the prefix-chain *head hashes* (the routing/prefix.py
   digests — never raw text), sampling params, output tokens, finish
   reason.  Records land in a bounded ring (dumped via
   ``/v1/debug/workload``) and optionally append-stream to the
   ``TPU_WORKLOAD_TRACE`` path.

2. **Trace tooling** — ``parse_trace`` reads a capture back (garbage lines
   are *counted as rejected*, never raised: a trace that survived a crash
   mid-line must still load), ``synth_trace`` generates seeded synthetic
   workloads (chat / embed / longctx / bursty agent tool-call loops), and
   ``prompt_text_for`` derives a deterministic prompt for a record that
   carries no raw ids — seeded from the chain head hash so prefix-sharing
   structure survives the round trip.  scripts/replay.py builds its request
   stream from these, which is what makes two seeded replays byte-identical.

3. **Latency waterfall** — the per-request ledger decomposing wall time
   into stages that sum *exactly* to the measured wall by construction:

     admit_wait       created -> admitted (submit queue + admission gate)
     shed             admission-shed backoff spent before submit landed
     prefill_compute  synchronous prefill dispatch walls attributed to the
                      request (token-share of each batch/chunk dispatch)
     prefill_queue    (admitted -> first token) minus prefill_compute —
                      time the prompt sat admitted but not on the device
     decode           first token -> finish, minus stall and preempt
     stall            inter-token gaps beyond TPU_WATERFALL_STALL_MS
     preempt          wall spent preempted (snapshot parked off-slot)

   ``LatencyWaterfall`` keeps percentile windows per stage, cumulative
   per-stage seconds (the ``llmtpu_latency_stage_seconds`` delta bridge in
   api/server.py reads these), and a recent-request ring for
   ``/v1/debug/latency``.

Like tracing/recorder/perf this module is deliberately stdlib-only and
must never import ``executor``, ``api``, ``routing``, ``jax`` or any
other subsystem: the engine imports *us* and hands plain scalars/lists.
``analysis/imports_lint.py`` pins that contract.

Knobs: ``TPU_WORKLOAD`` (default 1; ``=0`` is a true no-op),
``TPU_WORKLOAD_RING`` (ring capacity, default 8192),
``TPU_WORKLOAD_TRACE`` (append-stream capture path),
``TPU_WORKLOAD_IDS`` (default 0; ``=1`` embeds raw prompt token ids in
records — required for token-identical replay, off by default because ids
are reversible to text), and ``TPU_WATERFALL_STALL_MS`` (inter-token gap
beyond which decode time counts as stall, default 250).
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import threading
from collections import deque
from typing import Any, Iterable

__all__ = [
    "AGENT_TOOL_SCHEMAS",
    "LatencyWaterfall",
    "SCHEMA_VERSION",
    "STAGES",
    "WorkloadTrace",
    "get_workload",
    "load_trace",
    "parse_trace",
    "prompt_text_for",
    "set_workload",
    "stall_threshold_s",
    "synth_trace",
]

SCHEMA_VERSION = 1
DEFAULT_RING = 8192
# head hashes only: enough chain entries to see prefix-sharing structure
# without shipping the whole boundary list for an 8k prompt
CHAIN_HEAD = 8

# Canned tool-call schemas for the synthetic agent workload. These are
# CLOSED schemas — every field is an enum or boolean, so the constraint
# automaton's accepting state has no outgoing transitions and the mask
# forces EOS there. A grammar-constrained replay therefore terminates
# with valid JSON on ANY model (tests/test_constrain.py holds every schema
# here to that).
AGENT_TOOL_SCHEMAS: tuple = (
    {"type": "object", "properties": {
        "tool": {"enum": ["search", "fetch", "calc"]},
        "urgent": {"type": "boolean"},
    }},
    {"type": "object", "properties": {
        "action": {"enum": ["read", "write", "list"]},
        "confirm": {"enum": ["yes", "no"]},
    }},
    {"type": "object", "properties": {
        "op": {"enum": ["add", "mul", "div"]},
        "commit": {"type": "boolean"},
    }},
)

STAGES = (
    "admit_wait",
    "shed",
    "prefill_queue",
    "prefill_compute",
    "decode",
    "stall",
    "preempt",
)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def stall_threshold_s() -> float:
    """Inter-token gap beyond which decode wall counts as stall.

    Read per call so the knob works on a live process (recorder.py's
    enablement convention)."""
    return max(0.0, _env_float("TPU_WATERFALL_STALL_MS", 250.0)) / 1e3


def _pctl(vals: list[float], q: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    idx = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[idx]


# ---------------------------------------------------------------------------
# capture


class WorkloadTrace:
    """Bounded ring of per-request workload records + optional file stream."""

    def __init__(
        self,
        capacity: int | None = None,
        trace_path: str | None = None,
        include_ids: bool | None = None,
    ):
        cap = capacity if capacity is not None else _env_int("TPU_WORKLOAD_RING", DEFAULT_RING)
        self.capacity = max(16, cap)
        # None means "read the env per record" so the knobs work live
        self._trace_path = trace_path
        self._include_ids = include_ids
        self._ring: deque[dict] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.records_total = 0
        self.file_errors = 0

    def enabled(self) -> bool:
        """TPU_WORKLOAD=0 is a true no-op (checked per record, live knob)."""
        return os.environ.get("TPU_WORKLOAD", "1") not in ("0", "false", "no", "off")

    def _want_ids(self) -> bool:
        if self._include_ids is not None:
            return self._include_ids
        return os.environ.get("TPU_WORKLOAD_IDS", "0") not in ("", "0", "false", "no", "off")

    def trace_path(self) -> str:
        if self._trace_path is not None:
            return self._trace_path
        return os.environ.get("TPU_WORKLOAD_TRACE", "")

    def record(
        self,
        *,
        ts: float,
        rid: str,
        trace_id: str = "",
        model: str = "",
        prompt_tokens: int = 0,
        chain: Iterable[tuple[int, str]] = (),
        max_tokens: int = 0,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        output_tokens: int = 0,
        finish: str = "",
        ids: Iterable[int] | None = None,
        shed_s: float = 0.0,
    ) -> dict | None:
        """Append one admitted-request record; returns it (or None when off)."""
        if not self.enabled():
            return None
        rec: dict[str, Any] = {
            "v": SCHEMA_VERSION,
            "ts": float(ts),
            "rid": str(rid),
            "trace": str(trace_id or ""),
            "model": str(model),
            "pt": int(prompt_tokens),
            "chain": [[int(n), str(h)] for n, h in list(chain)[:CHAIN_HEAD]],
            "mt": int(max_tokens),
            "temp": float(temperature),
            "top_k": int(top_k),
            "top_p": float(top_p),
            "ot": int(output_tokens),
            "fin": str(finish),
        }
        if shed_s > 0:
            rec["shed_s"] = round(float(shed_s), 6)
        if ids is not None and self._want_ids():
            rec["ids"] = [int(t) for t in ids]
        with self._lock:
            self._ring.append(rec)
            self.records_total += 1
        path = self.trace_path()
        if path:
            try:
                with open(path, "a", encoding="utf-8") as fh:
                    fh.write(json.dumps(rec, separators=(",", ":")) + "\n")
            except OSError:
                self.file_errors += 1
        return rec

    def snapshot(self, limit: int = 200) -> list[dict]:
        """Newest-last copy of the ring tail."""
        with self._lock:
            rows = list(self._ring)
        return rows[-max(0, limit):] if limit else rows

    def stats(self) -> dict:
        with self._lock:
            ring_len = len(self._ring)
        return {
            "enabled": self.enabled(),
            "records_total": self.records_total,
            "ring": ring_len,
            "capacity": self.capacity,
            "trace_path": self.trace_path(),
            "file_errors": self.file_errors,
            "include_ids": self._want_ids(),
        }

    def dump(self, path: str) -> int:
        """Write the whole ring to `path` as JSONL; returns record count."""
        rows = self.snapshot(limit=0)
        with open(path, "w", encoding="utf-8") as fh:
            for rec in rows:
                fh.write(json.dumps(rec, separators=(",", ":")) + "\n")
        return len(rows)


_workload: WorkloadTrace | None = None
_workload_lock = threading.Lock()


def get_workload() -> WorkloadTrace:
    """Process-shared capture ring (recorder.py's get_recorder convention)."""
    global _workload
    with _workload_lock:
        if _workload is None:
            _workload = WorkloadTrace()
        return _workload


def set_workload(w: WorkloadTrace | None) -> None:
    global _workload
    with _workload_lock:
        _workload = w


# ---------------------------------------------------------------------------
# trace parsing


def _valid_record(rec: Any) -> bool:
    if not isinstance(rec, dict) or rec.get("v") != SCHEMA_VERSION:
        return False
    if not isinstance(rec.get("ts"), (int, float)):
        return False
    for key in ("pt", "mt", "ot", "top_k"):
        v = rec.get(key, 0)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            return False
    for key in ("temp", "top_p", "shed_s"):
        v = rec.get(key, 0.0)
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            return False
    chain = rec.get("chain", [])
    if not isinstance(chain, list):
        return False
    for entry in chain:
        if (
            not isinstance(entry, (list, tuple))
            or len(entry) != 2
            or not isinstance(entry[0], int)
            or not isinstance(entry[1], str)
        ):
            return False
    ids = rec.get("ids")
    if ids is not None and (
        not isinstance(ids, list)
        or any(not isinstance(t, int) or isinstance(t, bool) for t in ids)
    ):
        return False
    return True


def parse_trace(lines: Iterable[str]) -> tuple[list[dict], int]:
    """(records, rejected_count) from capture JSONL lines.

    Garbage — truncated JSON, wrong schema version, non-record rows — is
    *counted*, never raised: a trace file that survived a crash mid-write
    must still replay."""
    records: list[dict] = []
    rejected = 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            rejected += 1
            continue
        if _valid_record(rec):
            records.append(rec)
        else:
            rejected += 1
    return records, rejected


def load_trace(path: str) -> tuple[list[dict], int]:
    """parse_trace over a file, sorted by arrival timestamp."""
    with open(path, encoding="utf-8") as fh:
        records, rejected = parse_trace(fh)
    records.sort(key=lambda r: r["ts"])
    return records, rejected


# ---------------------------------------------------------------------------
# synthetic workload generators

_WORDS = (
    "the model reads a long context and answers with a short plan then "
    "calls a tool parses the result and continues the loop until the task "
    "is done or the budget runs out"
).split()


def _hash16(data: str) -> str:
    return hashlib.blake2b(data.encode(), digest_size=8).hexdigest()


def _synth_chain(pt: int, head_seed: str, block_tokens: int = 64) -> list[list]:
    """Deterministic chain-head boundary hashes for a synthetic prompt."""
    out: list[list] = []
    h = head_seed
    for i in range(min(CHAIN_HEAD, pt // block_tokens)):
        h = _hash16(h + str(i))
        out.append([(i + 1) * block_tokens, h])
    return out


def _mk(ts: float, i: int, kind: str, seed: int, *, pt: int, mt: int,
        temp: float, chain_seed: str) -> dict:
    return {
        "v": SCHEMA_VERSION,
        "ts": round(ts, 6),
        "rid": f"{kind[:2]}{seed:04x}{i:06x}",
        "trace": "",
        "model": "",
        "pt": pt,
        "chain": _synth_chain(pt, chain_seed),
        "mt": mt,
        "temp": temp,
        "top_k": 0,
        "top_p": 1.0,
        "ot": 0,
        "fin": "",
    }


def synth_trace(kind: str, n: int, seed: int = 0, start_ts: float = 0.0) -> list[dict]:
    """Seeded synthetic workload: same (kind, n, seed) -> byte-identical
    records, which is what makes two replays issue identical streams.

    kinds:
      chat    Poisson arrivals ~2 rps, short-to-medium prompts, sampled
      embed   dense bursts of short prompts, 1-token outputs (embedding-
              shaped traffic: all prefill, no decode)
      longctx sparse arrivals, 1k-8k prompts, short outputs
      agent   bursty tool-call loops: 3-8 requests per burst sharing one
              prefix chain (the conversation so far), think-time between.
              Each burst is one tool loop, so its records carry the SAME
              tool-call JSON schema under ``rec["schema"]`` (drawn from
              AGENT_TOOL_SCHEMAS), which a replay can send as a
              json_schema constraint
    """
    rng = random.Random((seed << 8) ^ len(kind))
    ts = float(start_ts)
    out: list[dict] = []
    if kind == "chat":
        for i in range(n):
            ts += rng.expovariate(2.0)
            out.append(_mk(ts, i, kind, seed,
                           pt=rng.randint(48, 512),
                           mt=rng.randint(32, 256),
                           temp=round(rng.uniform(0.5, 0.9), 2),
                           chain_seed=f"chat{seed}:{i}"))
    elif kind == "embed":
        i = 0
        while i < n:
            ts += rng.expovariate(0.5)
            for _ in range(min(rng.randint(8, 32), n - i)):
                ts += 0.002
                out.append(_mk(ts, i, kind, seed,
                               pt=rng.randint(16, 128), mt=1, temp=0.0,
                               chain_seed=f"embed{seed}:{i}"))
                i += 1
    elif kind == "longctx":
        for i in range(n):
            ts += rng.expovariate(0.25)
            out.append(_mk(ts, i, kind, seed,
                           pt=rng.randint(1024, 8192),
                           mt=rng.randint(32, 128),
                           temp=0.0,
                           chain_seed=f"longctx{seed}:{i}"))
    elif kind == "agent":
        i = 0
        burst = 0
        while i < n:
            ts += rng.uniform(2.0, 8.0)  # think-time between tool loops
            shared = f"agent{seed}:burst{burst}"
            # one tool per loop: every request in the burst emits a call
            # shaped by the same (closed) JSON schema
            sch = AGENT_TOOL_SCHEMAS[rng.randrange(len(AGENT_TOOL_SCHEMAS))]
            grow = 0
            for _ in range(min(rng.randint(3, 8), n - i)):
                ts += rng.uniform(0.05, 0.4)  # tool round-trip
                grow += rng.randint(64, 256)  # the loop's growing context
                rec = _mk(ts, i, kind, seed,
                          pt=256 + grow,
                          mt=rng.randint(16, 96),
                          temp=0.0,
                          chain_seed=shared)
                rec["schema"] = sch
                out.append(rec)
                i += 1
            burst += 1
    else:
        raise ValueError(f"unknown synthetic workload kind: {kind!r}"
                         " (chat/embed/longctx/agent)")
    return out


def prompt_text_for(rec: dict, words_per_token: float = 0.75) -> str:
    """Deterministic prompt text for a record that carries no raw ids.

    Seeded from the chain head hash (so records sharing a prefix chain get
    a shared textual prefix — the replay preserves prefix-cache structure)
    plus the rid for the unique tail.  Identical records -> identical
    text, which keeps two seeded replays byte-identical."""
    n_words = max(1, int(rec.get("pt", 1) * words_per_token))
    chain = rec.get("chain") or []
    parts: list[str] = []
    if chain:
        head = random.Random(chain[0][1])
        shared_words = max(1, int(n_words * min(1.0, len(chain) / CHAIN_HEAD)))
        parts.extend(_WORDS[head.randrange(len(_WORDS))] for _ in range(shared_words))
        n_words -= shared_words
    tail = random.Random(str(rec.get("rid", "")))
    parts.extend(_WORDS[tail.randrange(len(_WORDS))] for _ in range(n_words))
    return " ".join(parts)


# ---------------------------------------------------------------------------
# latency waterfall


class LatencyWaterfall:
    """Per-request latency ledger with exact-partition stages.

    The engine hands finished-request stage seconds (already clamped so
    they sum exactly to the request's measured wall); this class keeps the
    percentile windows, the cumulative per-stage totals the Prometheus
    delta bridge reads, and the recent-request ring /v1/debug/latency
    serves."""

    def __init__(self, window: int = 2048, recent: int = 128):
        self._lock = threading.Lock()
        self._windows: dict[str, deque[float]] = {
            s: deque(maxlen=window) for s in STAGES
        }
        self._total_window: deque[float] = deque(maxlen=window)
        self._stage_s: dict[str, float] = {s: 0.0 for s in STAGES}
        self._recent: deque[dict] = deque(maxlen=recent)
        self.requests = 0
        self.wall_s_total = 0.0

    def observe(
        self,
        stages: dict[str, float],
        total_s: float,
        trace_id: str = "",
        rid: str = "",
        ts: float = 0.0,
    ) -> None:
        with self._lock:
            self.requests += 1
            self.wall_s_total += max(0.0, total_s)
            self._total_window.append(max(0.0, total_s))
            row: dict[str, Any] = {
                "ts": round(ts, 6),
                "rid": rid,
                "trace": trace_id,
                "total_ms": round(total_s * 1e3, 3),
            }
            for s in STAGES:
                v = max(0.0, float(stages.get(s, 0.0)))
                self._stage_s[s] += v
                self._windows[s].append(v)
                row[f"{s}_ms"] = round(v * 1e3, 3)
            self._recent.append(row)

    def stage_seconds(self) -> dict[str, float]:
        """Cumulative seconds per stage — the engines_info delta bridge
        advances llmtpu_latency_stage_seconds from consecutive reads."""
        with self._lock:
            return dict(self._stage_s)

    def stats(self) -> dict:
        with self._lock:
            stage_s = dict(self._stage_s)
            pct = {
                s: {
                    "p50_ms": round(_pctl(list(w), 0.50) * 1e3, 3),
                    "p95_ms": round(_pctl(list(w), 0.95) * 1e3, 3),
                }
                for s, w in self._windows.items()
            }
            total_p95 = _pctl(list(self._total_window), 0.95)
            n = self.requests
            wall = self.wall_s_total
        covered = sum(stage_s.values())
        return {
            "requests": n,
            "stage_s": {s: round(v, 6) for s, v in stage_s.items()},
            "stages": pct,
            "total_p95_ms": round(total_p95 * 1e3, 3),
            "wall_s_total": round(wall, 6),
            # stages are an exact partition by construction; this ratio is
            # the acceptance check (must stay within 5% of 1.0)
            "coverage": round(covered / wall, 6) if wall > 0 else 1.0,
        }

    def recent(self, limit: int = 32) -> list[dict]:
        with self._lock:
            rows = list(self._recent)
        return rows[-max(0, limit):]
