"""Flight recorder, anomaly-triggered dumps, and the compile ledger.

Traces (tracing.py) answer "where did *this request* spend its time";
Prometheus (metrics.py) answers "how is the fleet doing on average".
Neither answers the post-mortem question: *what was the serve loop doing,
step by step, in the seconds before it misbehaved?*  This module is that
missing layer — a bounded ring of structured step events that every hot
subsystem appends to, frozen and journaled to disk the moment an anomaly
detector fires, plus a compile ledger that records every jit/bucket
compile (the ROADMAP item-5 cold-start baseline).

Like tracing.py this module is deliberately dependency-free (stdlib only)
and must never import `executor`, `api`, `jax`, or any other subsystem:
the instrumented layers import *us*; consumers (alert pipeline, on-demand
profiler capture) attach via callbacks instead of being imported here.
`tests/test_recorder.py` pins that contract.

Model
-----
An *event* is a tuple ``(seq, ts, etype, trace_id, fields)``:

  seq       monotonic step sequence (process-wide, from itertools.count —
            a single CPython bytecode op, so the hot path needs no lock)
  ts        wall-clock seconds
  etype     short event kind: admit / budget / chunk / pf_rag (packed
            ragged prefill, with true/padded token fields) / verify /
            decode / fused / fused_rag (ragged fused step; one of the
            three at a round's DISPATCH, with rid and rows) / fetch (the
            round's blocking read returned: rid, wait_ms) / emit (the
            round's tokens went to their streams: rid, rows dispatched,
            tokens delivered, text events put, rows held = tokens and no
            text, dur_ms; these five carry t = time.monotonic()) /
            admit_prog (one admission DISPATCHED as a device program of
            its own: aid = the engine's count of them, kind = batch (whole
            prompts, admit_fn) / cached (a prefix hit's rows) / chunk (a
            chunk group with nothing decoding), rows, rows_padded, bucket,
            true_tokens, padded_tokens = rows_padded x bucket, queued =
            requests the batch left in the queue, held_by = why it closed:
            queue_empty / no_slot / admit_batch / budget, wait_ms_max = its
            oldest request's arrival to here, after_rid = the newest round
            dispatched before it, t; `admit` is the per-request event at
            the first token) /
            admit_read (a batched admission's first tokens were read from
            the in-flight queue: aid, or rid = the mixed round that carried
            it where it took no program of its own, rows, after_rid = the
            newest round fetched before it, wait_ms, blocked = the read
            still had to wait for the device, t) /
            mixed (a full-batch decode round DISPATCHED whose first step
            carries whole prompts through its pass over the weights,
            mixed_round_fn, in place of decode: rid, rows, prompts,
            prompt_tokens, padded_tokens = the round's rung, queued,
            held_by = why the batch closed: queue_empty / no_slot /
            admit_batch / budget / own, t) / preempt /
            offload / restore / cow / pin / unpin / snap (paged ledger
            snapshot for preempt/offload) / pg_tbl (device
            block-table reset/rebuild, with the shared-row count) /
            pg_cow (physical boundary-block copy: pool row -> identity
            home) / prefix_out (fleet prefix-tier chain export, with
            token + byte counts) / prefix_in (pin-only prefix-tier
            import from a peer) / migrate_out / migrate_in / shed /
            watchdog /
            compile / perf (sampled host/device/wait phase timing from
            the perf observatory) / anomaly / profile / wl (workload
            capture: one record per finished admitted request —
            telemetry/workload.py) / wf (latency-waterfall stage marks:
            per-request admit_wait/shed/prefill_queue/prefill_compute/
            decode/stall/preempt milliseconds) / wu (one warmup-planner
            AOT compile: phase, key, wall, outcome) / warmup (readiness
            state transition: cold / first_token_ready / fully_warm —
            executor/warmup.py) / zoo (model-zoo catalog change:
            registration with residency — executor/zoo.py) / swap_in /
            swap_out (zoo residency moves, with byte counts and wall
            seconds: page parked host weights into HBM / park a resident
            engine's tree back to host RAM) / cn_cmp (one constraint
            compile at admission: cache miss flag, automaton states,
            wall — llm_mcp_tpu/constrain) / cnstep (one grammar-masked
            single-step decode round, with row count) / cn_spec (one
            constrained speculative verify round: drafted vs accepted
            token counts under per-position masks)
  trace_id  the request's 32-hex trace id ("" for engine-global events) —
            a dump stitches directly into /v1/traces
  fields    flat dict of scalars (or None)

The ring is a preallocated list; `event()` writes one slot with a single
item-assignment (atomic under the GIL) and never blocks, allocates
bounded memory, and never touches a lock.  `dump()` freezes appends just
long enough to copy the ring (microseconds), then journals the copy as
JSONL off to disk; events arriving while frozen are *counted as dropped*
rather than queued — the dropped counter is the health signal
`/metrics` exports (`llmtpu_flight_dropped_events` must stay 0).

Enablement follows tracing.py: on by default, `TPU_FLIGHT=0` disables
(checked per event, so the knob works on a live process and `=0` is a
true no-op — no ring writes, no dumps, no detector state).

Knobs: `TPU_FLIGHT` (default 1), `TPU_FLIGHT_RING` (ring capacity,
default 8192), `TPU_FLIGHT_DIR` (journal directory), and
`TPU_FLIGHT_DUMP_INTERVAL_S` (min seconds between anomaly dumps,
default 10).  `TPU_FLIGHT_PROFILE_STEPS` is read by the engine
(the jax.profiler hook lives there, not here).
"""

from __future__ import annotations

import itertools
import json
import os
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable

__all__ = [
    "AnomalyMonitor",
    "CompileLedger",
    "DecodeStallDetector",
    "FlightRecorder",
    "ITLDegradationDetector",
    "PagedLeakDetector",
    "PingPongDetector",
    "ShedDuringGraceDetector",
    "SpecCollapseDetector",
    "TTFTBurnDetector",
    "get_compile_ledger",
    "get_recorder",
    "set_compile_ledger",
    "set_recorder",
]

DEFAULT_RING = 8192
DEFAULT_DUMP_INTERVAL_S = 10.0
# What JAX itself reports inside a first dispatch (executor/compile_watch.py
# hands them in as plain numbers): seconds tracing, lowering, in the backend
# (compile, or load from the persistent cache), reading the cache, and the
# number of executables JAX asked the cache for.
COMPILE_PARTS = ("trace_s", "lower_s", "backend_s", "cache_load_s",
                 "compile_requests")

EVENT_KEYS = ("seq", "ts", "etype", "trace_id", "fields")


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


class FlightRecorder:
    """Bounded lock-free ring of step events + freeze-and-journal dumps."""

    def __init__(
        self,
        capacity: int | None = None,
        dump_dir: str | None = None,
        dump_interval_s: float | None = None,
    ):
        self.capacity = max(16, capacity if capacity is not None
                            else _env_int("TPU_FLIGHT_RING", DEFAULT_RING))
        self.dump_dir = dump_dir or os.environ.get("TPU_FLIGHT_DIR") or os.path.join(
            tempfile.gettempdir(), "llmtpu-flight"
        )
        self.dump_interval_s = (
            dump_interval_s if dump_interval_s is not None
            else _env_float("TPU_FLIGHT_DUMP_INTERVAL_S", DEFAULT_DUMP_INTERVAL_S)
        )
        # Preallocated ring. The hot path does ONE item-assignment into it;
        # list item assignment is atomic under the GIL, so no lock and no
        # allocation beyond the event tuple itself.
        self._ring: list[tuple | None] = [None] * self.capacity
        self._seq = itertools.count()  # next(counter) is a single atomic op
        self._frozen = False           # set only inside dump()'s copy window
        self._dropped = 0
        self._dumps = 0
        self._last_dump_ts = 0.0
        self._last_dump_path = ""
        self._dump_lock = threading.Lock()   # dump/snapshot only — never event()
        self._on_dump: list[Callable[[dict], None]] = []

    # -- enablement --------------------------------------------------------

    @property
    def enabled(self) -> bool:
        """Dynamic so TPU_FLIGHT can be flipped on a live process."""
        return os.environ.get("TPU_FLIGHT", "1").strip().lower() not in (
            "0", "false", "off", "no",
        )

    # -- hot path ----------------------------------------------------------

    def event(self, etype: str, trace_id: str = "", **fields: Any) -> None:
        """Append one step event. Never blocks, never raises, never locks:
        when the ring is frozen mid-dump the event is dropped and counted
        (the perf gate hard-fails on a nonzero drop count, so the freeze
        window is sized in microseconds)."""
        if not self.enabled:
            return
        if self._frozen:
            self._dropped += 1
            return
        seq = next(self._seq)
        self._ring[seq % self.capacity] = (
            seq, time.time(), etype, trace_id, fields or None,
        )

    # -- read side ---------------------------------------------------------

    def _copy(self) -> list[tuple]:
        """Ring contents in sequence order. Tuples are immutable and slots
        are replaced whole, so a plain list() copy yields only intact
        events (possibly spanning a wrap — sorting by seq fixes order)."""
        rows = [r for r in list(self._ring) if r is not None]
        rows.sort(key=lambda r: r[0])
        return rows

    def snapshot(self, limit: int = 0, etype: str = "") -> list[dict[str, Any]]:
        """Newest-last event dicts for /v1/debug/flight (no freeze)."""
        rows = self._copy()
        if etype:
            rows = [r for r in rows if r[2] == etype]
        if limit > 0:
            rows = rows[-limit:]
        return [dict(zip(EVENT_KEYS, r)) for r in rows]

    def events_total(self) -> int:
        """Sequence high-water mark == events accepted so far."""
        # itertools.count has no peek; track via a throwaway clone of the
        # ring head instead: the max seq present, +1. Empty ring → 0.
        rows = [r for r in list(self._ring) if r is not None]
        return (max(r[0] for r in rows) + 1) if rows else 0

    @property
    def dropped_events(self) -> int:
        return self._dropped

    def stats(self) -> dict[str, Any]:
        return {
            "enabled": self.enabled,
            "capacity": self.capacity,
            "events_total": self.events_total(),
            "dropped_events": self._dropped,
            "dumps": self._dumps,
            "last_dump_ts": self._last_dump_ts,
            "last_dump_path": self._last_dump_path,
        }

    # -- dumps -------------------------------------------------------------

    def add_dump_callback(self, fn: Callable[[dict], None]) -> None:
        """fn(info) fires after each journal lands on disk. Exceptions are
        swallowed. The alert pipeline and the engine's on-demand profiler
        capture attach here so this module stays import-free."""
        if fn not in self._on_dump:
            self._on_dump.append(fn)

    def remove_dump_callback(self, fn: Callable[[dict], None]) -> None:
        if fn in self._on_dump:
            self._on_dump.remove(fn)

    def dump(self, reason: str, detector: str = "", force: bool = False) -> str | None:
        """Freeze-copy-unfreeze the ring, then journal the copy as JSONL.

        The freeze covers only the in-memory copy (a list() of the ring),
        not the disk write — appenders racing the copy are counted as
        dropped rather than blocked.  Rate-limited by dump_interval_s
        unless force=True.  Returns the journal path, or None when
        disabled / rate-limited / the disk said no."""
        if not self.enabled:
            return None
        with self._dump_lock:
            now = time.time()
            if not force and now - self._last_dump_ts < self.dump_interval_s:
                return None
            self._frozen = True
            try:
                rows = self._copy()
            finally:
                self._frozen = False
            self._last_dump_ts = now
            self._dumps += 1
            path = os.path.join(
                self.dump_dir,
                f"flight-{time.strftime('%Y%m%d-%H%M%S', time.gmtime(now))}"
                f"-{self._dumps:04d}.jsonl",
            )
            header = {
                "kind": "flight_dump",
                "ts": now,
                "reason": reason,
                "detector": detector,
                "events": len(rows),
                "dropped_events": self._dropped,
                "capacity": self.capacity,
            }
            try:
                os.makedirs(self.dump_dir, exist_ok=True)
                with open(path, "w", encoding="utf-8") as f:
                    f.write(json.dumps(header) + "\n")
                    for r in rows:
                        f.write(json.dumps(dict(zip(EVENT_KEYS, r))) + "\n")
            except OSError:
                return None
            self._last_dump_path = path
        info = dict(header, path=path)
        for fn in list(self._on_dump):
            try:
                fn(info)
            except Exception:  # noqa: BLE001 — callbacks never break dumps
                pass
        return path


# -- anomaly detectors ------------------------------------------------------
# Pure state machines over scalar signals: observe(...) returns a reason
# string on the rising edge and None otherwise. Each latches after firing
# and re-arms only when its signal recovers, so one anomaly *episode*
# produces exactly one dump however often the engine polls.


class DecodeStallDetector:
    """Decode cadence stopped while work is in flight. The gap threshold is
    the larger of an absolute floor and a multiple of the scheduler's
    decode-round EMA, so slow-but-moving big batches don't false-positive."""

    name = "decode_stall"

    def __init__(self, min_gap_s: float = 2.0, ema_mult: float = 20.0):
        self.min_gap_s = min_gap_s
        self.ema_mult = ema_mult
        self._latched = False

    def observe(self, gap_s: float, ema_s: float, busy: int) -> str | None:
        stalled = busy > 0 and gap_s > max(self.min_gap_s, self.ema_mult * ema_s)
        if not stalled:
            self._latched = False
            return None
        if self._latched:
            return None
        self._latched = True
        return (f"decode cadence stalled: {gap_s:.2f}s since last round "
                f"(ema {ema_s * 1000:.0f}ms, {busy} in flight)")


class TTFTBurnDetector:
    """K consecutive TTFT samples over M× the TPU_TARGET_TTFT_MS SLO."""

    name = "ttft_burn"

    def __init__(self, target_ms: float, mult: float = 3.0, k: int = 4):
        self.target_ms = target_ms
        self.mult = mult
        self.k = max(1, k)
        self._over = 0
        self._latched = False

    def observe(self, ttft_ms: float) -> str | None:
        if self.target_ms <= 0:
            return None
        if ttft_ms <= self.mult * self.target_ms:
            self._over = 0
            self._latched = False
            return None
        self._over += 1
        if self._over < self.k or self._latched:
            return None
        self._latched = True
        return (f"TTFT SLO burn: {self._over} consecutive samples over "
                f"{self.mult:g}x target ({ttft_ms:.0f}ms vs {self.target_ms:.0f}ms)")


class SpecCollapseDetector:
    """Speculative accept rate collapsed over a window of verify rounds
    (the drafter is burning verify budget for nothing)."""

    name = "spec_collapse"

    def __init__(self, window: int = 32, min_rate: float = 0.05,
                 min_drafted: int = 64):
        self.window = deque(maxlen=max(4, window))
        self.min_rate = min_rate
        self.min_drafted = min_drafted
        self._latched = False

    def observe(self, drafted: int, accepted: int) -> str | None:
        if drafted <= 0:
            return None
        self.window.append((drafted, accepted))
        d = sum(w[0] for w in self.window)
        a = sum(w[1] for w in self.window)
        if d < self.min_drafted:
            return None
        rate = a / d
        if rate >= self.min_rate:
            self._latched = False
            return None
        if self._latched:
            return None
        self._latched = True
        return (f"speculative accept collapse: {rate:.1%} over last "
                f"{len(self.window)} verify rounds ({a}/{d})")


class PagedLeakDetector:
    """Paged-block leak count grew (audit() found unreferenced blocks).
    Re-fires only on further growth, not on a stable nonzero count."""

    name = "paged_leak"

    def __init__(self):
        self._high = 0

    def observe(self, leak_count: int) -> str | None:
        if leak_count <= self._high:
            if leak_count == 0:
                self._high = 0
            return None
        prev, self._high = self._high, leak_count
        return f"paged block leak growth: {prev} -> {leak_count} leaked blocks"


class PingPongDetector:
    """The same request migrated more than `max_hops` times inside
    `window_s` — the drain policy is shuttling KV back and forth."""

    name = "migration_pingpong"

    def __init__(self, max_hops: int = 2, window_s: float = 60.0,
                 max_tracked: int = 512):
        self.max_hops = max(1, max_hops)
        self.window_s = window_s
        self._hops: dict[str, deque] = {}
        self._order: deque = deque(maxlen=max_tracked)
        self._fired: set[str] = set()

    def observe(self, request_id: str, now: float | None = None) -> str | None:
        now = time.time() if now is None else now
        dq = self._hops.get(request_id)
        if dq is None:
            self._hops[request_id] = dq = deque()
            self._order.append(request_id)
            while len(self._hops) > self._order.maxlen:
                old = self._order.popleft()
                self._hops.pop(old, None)
                self._fired.discard(old)
        dq.append(now)
        while dq and now - dq[0] > self.window_s:
            dq.popleft()
        if len(dq) <= self.max_hops or request_id in self._fired:
            return None
        self._fired.add(request_id)
        return (f"migration ping-pong: request {request_id} moved "
                f"{len(dq)} times in {self.window_s:.0f}s")


class ITLDegradationDetector:
    """Windowed mean inter-token latency breached M× the TPU_TARGET_ITL_MS
    SLO. TTFTBurnDetector's decode-side sibling: the burn case it catches
    is tokens still flowing but *slowly* — a decode stall never trips
    (cadence stops entirely), yet users see exactly this as sluggish
    streaming. Fed per-round (itl_ms = round gap / tokens learned), it
    needs min_samples before judging so one coalesced round can't fire it."""

    name = "itl_degradation"

    def __init__(self, target_ms: float, mult: float = 3.0,
                 window: int = 64, min_samples: int = 16):
        self.target_ms = target_ms
        self.mult = mult
        self.window = deque(maxlen=max(4, window))
        self.min_samples = max(1, min_samples)
        self._latched = False

    def observe(self, itl_ms: float) -> str | None:
        if self.target_ms <= 0:
            return None
        self.window.append(itl_ms)
        if len(self.window) < self.min_samples:
            return None
        mean = sum(self.window) / len(self.window)
        if mean <= self.mult * self.target_ms:
            self._latched = False
            return None
        if self._latched:
            return None
        self._latched = True
        return (f"ITL degradation: mean {mean:.1f}ms over last "
                f"{len(self.window)} rounds vs {self.mult:g}x target "
                f"({self.target_ms:.0f}ms)")


class ShedDuringGraceDetector:
    """Load was shed while the watchdog's compile-grace window was active —
    the engine dropped work because of a *compile*, not a wedge. One fire
    per grace episode."""

    name = "shed_in_grace"

    def __init__(self):
        self._latched = False

    def observe(self, in_grace: bool, shed: int) -> str | None:
        if not in_grace:
            self._latched = False
            return None
        if shed <= 0 or self._latched:
            return None
        self._latched = True
        return f"shed {shed} request(s) during compile grace window"


class AnomalyMonitor:
    """Routes raw engine signals to the detector set; on a rising edge it
    journals the flight ring, appends to the anomaly history, and fires
    observer callbacks (the engine bridges these to the alert pipeline
    and the on-demand profiler)."""

    def __init__(
        self,
        recorder: FlightRecorder,
        detectors: list | None = None,
        history: int = 64,
        target_ttft_ms: float | None = None,
        target_itl_ms: float | None = None,
    ):
        self.recorder = recorder
        if detectors is None:
            if target_ttft_ms is None:
                target_ttft_ms = _env_float("TPU_TARGET_TTFT_MS", 0.0)
            if target_itl_ms is None:
                target_itl_ms = _env_float("TPU_TARGET_ITL_MS", 0.0)
            detectors = [
                DecodeStallDetector(),
                TTFTBurnDetector(target_ms=target_ttft_ms),
                ITLDegradationDetector(target_ms=target_itl_ms),
                SpecCollapseDetector(),
                PagedLeakDetector(),
                PingPongDetector(),
                ShedDuringGraceDetector(),
            ]
        self._detectors = {d.name: d for d in detectors}
        self._history: deque = deque(maxlen=max(4, history))
        self._counts: dict[str, int] = {}
        self._callbacks: list[Callable[[dict], None]] = []

    def add_callback(self, fn: Callable[[dict], None]) -> None:
        if fn not in self._callbacks:
            self._callbacks.append(fn)

    def signal(self, kind: str, **fields: Any) -> dict[str, Any] | None:
        """Feed one signal sample to detector `kind`. Returns the anomaly
        record on a rising edge, else None. Unknown kinds and disabled
        recorders are no-ops so call sites need no guards."""
        det = self._detectors.get(kind)
        if det is None or not self.recorder.enabled:
            return None
        try:
            reason = det.observe(**fields)
        except TypeError:
            return None  # malformed signal never breaks the serve loop
        if not reason:
            return None
        return self._fire(kind, reason)

    def _fire(self, kind: str, reason: str) -> dict[str, Any]:
        self.recorder.event("anomaly", detector=kind, reason=reason)
        path = self.recorder.dump(reason=reason, detector=kind)
        entry = {
            "ts": time.time(),
            "detector": kind,
            "reason": reason,
            "journal": path or "",
        }
        self._history.append(entry)
        self._counts[kind] = self._counts.get(kind, 0) + 1
        for fn in list(self._callbacks):
            try:
                fn(entry)
            except Exception:  # noqa: BLE001
                pass
        return entry

    def history(self, limit: int = 20) -> list[dict[str, Any]]:
        items = list(self._history)
        return items[-max(1, int(limit)):][::-1]

    def stats(self) -> dict[str, Any]:
        return {
            "dumps_total": sum(self._counts.values()),
            "by_detector": dict(self._counts),
            "last": self._history[-1] if self._history else None,
        }


# -- compile ledger ---------------------------------------------------------


class CompileLedger:
    """Every first dispatch of an executable shape, as (phase, bucket key,
    wall seconds, source) and what JAX reported inside it: seconds tracing,
    lowering and in the backend, seconds reading the persistent cache, the
    number of compile requests, and whether they were cache hits. Entries
    land in a bounded deque; per-key aggregates build the queryable table
    /v1/debug/compiles serves; the metrics layer drains new entries into
    `llmtpu_compile_seconds`.

    `hit` is JAX's own answer (`parts["hit"]`: every compile request of the
    dispatch was served from the persistent cache), or an explicit `hit=`
    from a caller that knows; None where JAX made no compile request (the
    dispatch found its executable in memory, or the cache is off)."""

    def __init__(self, max_entries: int = 512):
        self._lock = threading.Lock()
        self._entries: deque = deque(maxlen=max(16, max_entries))
        self._by_key: dict[str, dict[str, Any]] = {}
        self._fresh: deque = deque(maxlen=max(16, max_entries))
        self._total_s = 0.0
        self._parts: dict[str, dict[str, float]] = {}  # src -> sums

    @staticmethod
    def _add_parts(sums: dict[str, dict[str, float]], entry: dict) -> None:
        row = sums.setdefault(entry["src"], {
            "entries": 0, "wall_s": 0.0, **{k: 0.0 for k in COMPILE_PARTS}})
        row["entries"] += 1
        row["wall_s"] = round(row["wall_s"] + entry["wall_s"], 6)
        for k in COMPILE_PARTS:
            row[k] = round(row[k] + entry[k], 6)

    def observe(self, phase: str, key: str, wall_s: float,
                hit: bool | None = None, src: str = "serve",
                parts: dict[str, Any] | None = None) -> dict[str, Any]:
        """`src` is provenance: which path paid (or skipped) this compile —
        "serve" (first real dispatch), "warmup" (AOT warmup planner), or
        "import" (a warmup-pack plan entry adopted without compiling).
        Per-entry so /v1/debug/compiles can show whether the serve path
        ever ate a cold compile that warmup should have absorbed. `parts`
        is what JAX reported on the dispatching thread (COMPILE_PARTS and
        `hit`); absent where nobody listened."""
        parts = parts or {}
        if hit is None:
            hit = parts.get("hit")
        entry = {
            "ts": time.time(),
            "t": time.monotonic(),
            "phase": phase,
            "key": key,
            "wall_s": round(float(wall_s), 6),
            "hit": None if hit is None else bool(hit),
            "src": str(src),
            **{k: round(float(parts.get(k, 0.0)), 6) for k in COMPILE_PARTS},
        }
        with self._lock:
            self._entries.append(entry)
            self._fresh.append(entry)
            self._total_s += wall_s
            agg = self._by_key.get(key)
            if agg is None:
                self._by_key[key] = agg = {
                    "key": key, "phase": phase, "count": 0,
                    "hits": 0, "misses": 0, "total_s": 0.0, "max_s": 0.0,
                    "by_src": {}, "parts": {},
                }
            agg["count"] += 1
            if hit is not None:
                agg["hits" if hit else "misses"] += 1
            agg["total_s"] = round(agg["total_s"] + wall_s, 6)
            agg["max_s"] = round(max(agg["max_s"], float(wall_s)), 6)
            agg["by_src"][entry["src"]] = agg["by_src"].get(entry["src"], 0) + 1
            self._add_parts(agg["parts"], entry)
            self._add_parts(self._parts, entry)
        return entry

    def table(self) -> list[dict[str, Any]]:
        """Per-bucket aggregates, costliest first. `parts` holds, by
        source, the walls and what JAX reported inside them."""
        with self._lock:
            rows = [dict(v, by_src=dict(v["by_src"]),
                         parts={s: dict(p) for s, p in v["parts"].items()})
                    for v in self._by_key.values()]
        return sorted(rows, key=lambda r: -r["total_s"])

    def entries(self, limit: int = 100) -> list[dict[str, Any]]:
        with self._lock:
            rows = list(self._entries)
        return rows[-max(1, int(limit)):]

    def drain_fresh(self) -> list[dict[str, Any]]:
        """Entries observed since the last drain — the metrics bridge feeds
        these to the llmtpu_compile_seconds histogram exactly once."""
        with self._lock:
            rows = list(self._fresh)
            self._fresh.clear()
        return rows

    def stats(self) -> dict[str, Any]:
        with self._lock:
            n = len(self._entries)
            hits = sum(1 for e in self._entries if e["hit"])
            misses = sum(1 for e in self._entries if e["hit"] is False)
            shapes = len(self._by_key)
            total = self._total_s
            by_src: dict[str, int] = {}
            for e in self._entries:
                s = e.get("src", "serve")
                by_src[s] = by_src.get(s, 0) + 1
            parts = {s: dict(p) for s, p in self._parts.items()}
        return {
            "entries": n,
            "hits": hits,
            "misses": misses,
            "shapes": shapes,
            "total_s": round(total, 6),
            "by_src": by_src,
            # lifetime sums by source: wall_s and COMPILE_PARTS
            "parts": parts,
        }


# -- module-level defaults --------------------------------------------------
# One shared recorder + ledger per process so all engines, the API layer,
# and worker threads land events in the same ring (which /v1/debug/flight
# serves), mirroring tracing.get_tracer().

_default_recorder: FlightRecorder | None = None
_default_ledger: CompileLedger | None = None
_default_lock = threading.Lock()


def get_recorder() -> FlightRecorder:
    global _default_recorder
    if _default_recorder is None:
        with _default_lock:
            if _default_recorder is None:
                _default_recorder = FlightRecorder()
    return _default_recorder


def set_recorder(recorder: FlightRecorder) -> FlightRecorder:
    """Swap the process-default recorder (tests use this for isolation).
    Returns the previous recorder."""
    global _default_recorder
    with _default_lock:
        prev = _default_recorder
        _default_recorder = recorder
    return prev if prev is not None else recorder


def get_compile_ledger() -> CompileLedger:
    global _default_ledger
    if _default_ledger is None:
        with _default_lock:
            if _default_ledger is None:
                _default_ledger = CompileLedger()
    return _default_ledger


def set_compile_ledger(ledger: CompileLedger) -> CompileLedger:
    global _default_ledger
    with _default_lock:
        prev = _default_ledger
        _default_ledger = ledger
    return prev if prev is not None else ledger
