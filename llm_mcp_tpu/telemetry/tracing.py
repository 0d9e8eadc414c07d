"""Dapper-style request tracing: spans, context propagation, trace ring.

Aggregate metrics (metrics.py) say the fleet is slow; traces say *which
request* was slow and *where* — queue wait vs. route vs. prefill vs. decode
vs. cloud fallback. This module is deliberately dependency-free (stdlib
only) and must never import `executor`, `api`, or any other subsystem: the
instrumented layers import *us*, and consumers (stage histograms, slow-trace
alerts) attach via `Tracer.add_observer` instead of being imported here.

Model
-----
A *span* is a named interval with a 128-bit trace id, a 64-bit span id, an
optional parent span id, a wall-clock start and a monotonic-derived
duration, and a flat string→scalar attribute dict.  Completed spans land in
a bounded in-memory ring keyed by trace id (oldest trace evicted first);
traces are never formally "closed", which keeps the model robust to spans
arriving out of order from multiple processes and threads.

Propagation uses the W3C `traceparent` wire format
(`00-<32 hex trace id>-<16 hex span id>-01`) carried in HTTP headers, gRPC
invocation metadata, and job payloads (`payload["_traceparent"]`).

In-process, the *current* span is tracked on a module-level thread-local
stack so nested `span()` blocks parent implicitly and helpers like
`current_traceparent()` work from anywhere on the request thread.

Engine span attribute vocabulary (the executor stamps these; consumers like
`scripts/trace_dump.py` and the stage histograms key on them):

  engine.admit    request_id
  engine.prefill  request_id, prompt_tokens, ttft_ms,
                  prefill_token_budget, sched_starved_rounds
  engine.decode   request_id, completion_tokens, tok_per_s, finish_reason;
                  with self-speculative decoding on (TPU_SPEC), also
                  spec_drafted / spec_accepted — the stream's draft-and-
                  verify contribution, explaining its tok_per_s

Tracing is on by default and globally disabled with `TPU_TRACE=0`; the
check is dynamic (read per span start) so tests and operators can flip it
on a live process.  `TPU_TRACE_FILE=<path>` appends every completed span
as one JSON line (the format `scripts/trace_dump.py` reads back).
"""

from __future__ import annotations

import ctypes
import json
import os
import re
import threading
import time
import uuid
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, Callable, Iterator

__all__ = [
    "NEW_TRACE",
    "Span",
    "Tracer",
    "UNTRACED_PATHS",
    "current_span",
    "current_traceparent",
    "format_traceparent",
    "get_tracer",
    "parse_traceparent",
    "pop_span",
    "push_span",
    "set_tracer",
]

TRACEPARENT_RE = re.compile(r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$")

DEFAULT_MAX_TRACES = 512
# Explicit parent sentinel: start a fresh root trace even when the calling
# thread already has an active span (HTTP dispatch uses this when no inbound
# traceparent header is present).
NEW_TRACE = object()
# Probe endpoints would otherwise evict every interesting trace from the ring.
UNTRACED_PATHS = frozenset({"/health", "/metrics"})


try:  # resolved once: handler threads name themselves on every request
    _prctl = ctypes.CDLL(None).prctl
except (OSError, AttributeError):  # not Linux
    _prctl = None


def name_os_thread(name: str) -> None:
    """Give the calling thread an OS-level name (Linux, 15 bytes). A profiler
    trace names a host line after its thread's OS name, and Python's own
    thread names do not reach the OS: every Python thread's line reads
    `python3`."""
    if _prctl is not None:
        _prctl(15, name.encode()[:15], 0, 0, 0)  # PR_SET_NAME


def _new_trace_id() -> str:
    return uuid.uuid4().hex


def _new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def parse_traceparent(value: str | None) -> tuple[str, str] | None:
    """`traceparent` header/metadata/payload value → (trace_id, span_id),
    or None when absent or malformed (malformed context starts a new trace
    rather than erroring the request)."""
    if not value:
        return None
    m = TRACEPARENT_RE.match(value.strip().lower())
    if not m:
        return None
    trace_id, span_id = m.group(1), m.group(2)
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None  # all-zero ids are invalid per W3C
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


class Span:
    """One timed interval. Created via Tracer.span()/start_span(); `end()`
    computes the duration from a monotonic clock and hands the span to the
    tracer's ring + observers."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name",
        "start", "duration_s", "attrs", "status",
        "_t0", "_tracer", "_ended",
    )

    def __init__(
        self,
        tracer: "Tracer | None",
        name: str,
        trace_id: str,
        parent_id: str = "",
        attrs: dict[str, Any] | None = None,
    ):
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.name = name
        self.start = time.time()
        self.duration_s = 0.0
        self.attrs: dict[str, Any] = dict(attrs) if attrs else {}
        self.status = "ok"
        self._t0 = time.monotonic()
        self._tracer = tracer
        self._ended = False

    # -- mutation ----------------------------------------------------------

    def set_attr(self, key: str, value: Any) -> "Span":
        self.attrs[key] = value
        return self

    def set_attrs(self, attrs: dict[str, Any]) -> "Span":
        self.attrs.update(attrs)
        return self

    def set_error(self, message: str) -> "Span":
        self.status = "error"
        self.attrs["error"] = message
        return self

    def end(self) -> None:
        if self._ended:
            return
        self._ended = True
        self.duration_s = time.monotonic() - self._t0
        if self._tracer is not None:
            self._tracer._finish(self)

    # -- context -----------------------------------------------------------

    @property
    def traceparent(self) -> str:
        return format_traceparent(self.trace_id, self.span_id)

    def to_dict(self) -> dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "duration_s": round(self.duration_s, 6),
            "status": self.status,
            "attrs": self.attrs,
        }


class _NoopSpan(Span):
    """Returned when tracing is disabled: absorbs the full Span API, never
    reaches the ring or observers."""

    def __init__(self):
        super().__init__(None, "", "")

    def set_attr(self, key: str, value: Any) -> "Span":
        return self

    def set_attrs(self, attrs: dict[str, Any]) -> "Span":
        return self

    def set_error(self, message: str) -> "Span":
        return self

    def end(self) -> None:
        pass

    @property
    def traceparent(self) -> str:
        return ""


_NOOP = _NoopSpan()

# Module-level (not per-Tracer) so swapping the default tracer mid-session
# never orphans a thread's active span stack.
_ctx = threading.local()


def _stack() -> list[Span]:
    try:
        return _ctx.stack
    except AttributeError:
        _ctx.stack = []
        return _ctx.stack


def current_span() -> Span | None:
    """The innermost live span on this thread, or None."""
    st = _stack()
    return st[-1] if st else None


def current_traceparent() -> str:
    """Wire context for the innermost live span on this thread ("" when no
    span is active — callers propagate only truthy values)."""
    sp = current_span()
    return sp.traceparent if sp is not None else ""


def push_span(span: Span) -> None:
    """Make `span` the thread's current span (explicit-lifetime callers like
    HTTP dispatch; prefer the span() context manager)."""
    if not isinstance(span, _NoopSpan):
        _stack().append(span)


def pop_span(span: Span) -> None:
    st = _stack()
    if st and st[-1] is span:
        st.pop()
    elif span in st:  # defensive: out-of-order exit
        st.remove(span)


ParentLike = "Span | str | tuple[str, str] | None"


class Tracer:
    def __init__(
        self,
        max_traces: int = DEFAULT_MAX_TRACES,
        export_path: str | None = None,
    ):
        self.max_traces = max(1, int(max_traces))
        self._export_path = (
            export_path if export_path is not None else os.environ.get("TPU_TRACE_FILE")
        )
        self._lock = threading.Lock()
        # trace_id → list of completed span dicts, oldest trace first
        self._traces: OrderedDict[str, list[dict[str, Any]]] = OrderedDict()
        self._observers: list[Callable[[Span], None]] = []

    # -- enablement --------------------------------------------------------

    @property
    def enabled(self) -> bool:
        """Dynamic so TPU_TRACE can be flipped on a live process."""
        return os.environ.get("TPU_TRACE", "1").strip().lower() not in (
            "0", "false", "off", "no",
        )

    # -- span creation -----------------------------------------------------

    def _resolve_parent(self, parent: Any) -> tuple[str, str]:
        """parent (Span | traceparent str | (trace_id, span_id) | None) →
        (trace_id, parent_span_id); None falls back to the thread's current
        span, else a fresh root trace."""
        if parent is NEW_TRACE:
            return _new_trace_id(), ""
        if parent is None:
            parent = current_span()
        if parent is None:
            return _new_trace_id(), ""
        if isinstance(parent, Span):
            if isinstance(parent, _NoopSpan):
                return _new_trace_id(), ""
            return parent.trace_id, parent.span_id
        if isinstance(parent, tuple):
            return parent[0], parent[1]
        ids = parse_traceparent(str(parent))
        if ids is None:
            return _new_trace_id(), ""
        return ids

    def start_span(
        self,
        name: str,
        parent: Any = None,
        attrs: dict[str, Any] | None = None,
    ) -> Span:
        """Start a span WITHOUT pushing it on the thread-local stack (for
        explicitly-managed lifetimes). Prefer the span() context manager."""
        if not self.enabled:
            return _NOOP
        trace_id, parent_id = self._resolve_parent(parent)
        return Span(self, name, trace_id, parent_id, attrs)

    @contextmanager
    def span(
        self,
        name: str,
        parent: Any = None,
        attrs: dict[str, Any] | None = None,
    ) -> Iterator[Span]:
        """Context-managed span, pushed on the thread-local stack so nested
        spans (and cross-layer helpers) parent to it implicitly."""
        sp = self.start_span(name, parent, attrs)
        if sp is _NOOP:
            yield sp
            return
        push_span(sp)
        try:
            yield sp
        except Exception as e:
            sp.set_error(f"{type(e).__name__}: {e}")
            raise
        finally:
            pop_span(sp)
            sp.end()

    def record(
        self,
        name: str,
        start: float,
        end: float,
        parent: Any = None,
        attrs: dict[str, Any] | None = None,
    ) -> Span | None:
        """Retroactively record a completed interval from wall-clock
        timestamps already measured elsewhere (the engine stamps
        created/admitted/first-token times on its own thread; spans are
        reconstructed after the fact). Returns the recorded span, or None
        when tracing is disabled or the interval is degenerate."""
        if not self.enabled or end < start:
            return None
        trace_id, parent_id = self._resolve_parent(parent)
        sp = Span(self, name, trace_id, parent_id, attrs)
        sp.start = start
        sp._ended = True
        sp.duration_s = end - start
        self._finish(sp)
        return sp

    # -- completion / storage ----------------------------------------------

    def _finish(self, span: Span) -> None:
        doc = span.to_dict()
        with self._lock:
            bucket = self._traces.get(span.trace_id)
            if bucket is None:
                self._traces[span.trace_id] = bucket = []
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)
            bucket.append(doc)
        for fn in list(self._observers):
            try:
                fn(span)
            except Exception:  # noqa: BLE001 — observers never break requests
                pass
        path = self._export_path
        if path:
            try:
                with self._lock:
                    with open(path, "a", encoding="utf-8") as f:
                        f.write(json.dumps(doc) + "\n")
            except OSError:
                self._export_path = None  # disk said no; stop trying

    # -- observers ---------------------------------------------------------

    def add_observer(self, fn: Callable[[Span], None]) -> None:
        """fn(span) is called after every span completes. Exceptions are
        swallowed. Used by the metrics layer (stage histograms) and the
        alert monitor (slow-trace hook) so this module stays import-free."""
        if fn not in self._observers:
            self._observers.append(fn)

    def remove_observer(self, fn: Callable[[Span], None]) -> None:
        if fn in self._observers:
            self._observers.remove(fn)

    # -- read side (/v1/traces) --------------------------------------------

    def get_trace(self, trace_id: str) -> list[dict[str, Any]]:
        """All completed spans of one trace, oldest start first."""
        with self._lock:
            spans = list(self._traces.get(trace_id) or ())
        return sorted(spans, key=lambda d: d["start"])

    def traces(self, limit: int = 50) -> list[dict[str, Any]]:
        """Newest-first trace summaries for the dashboard list view."""
        with self._lock:
            items = [(tid, list(spans)) for tid, spans in self._traces.items()]
        out = []
        for tid, spans in reversed(items[-max(1, int(limit)):]):
            if not spans:
                continue
            roots = [s for s in spans if not s["parent_id"]]
            head = min(roots or spans, key=lambda d: d["start"])
            t0 = min(s["start"] for s in spans)
            t1 = max(s["start"] + s["duration_s"] for s in spans)
            out.append({
                "trace_id": tid,
                "name": head["name"],
                "start": t0,
                "duration_s": round(t1 - t0, 6),
                "spans": len(spans),
                "status": "error" if any(s["status"] == "error" for s in spans) else "ok",
            })
        return out

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()


# -- module-level default tracer ------------------------------------------
# One shared tracer per process so API threads, the engine loop, and worker
# threads all land spans in the same ring (which /v1/traces serves).

_default: Tracer | None = None
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Tracer()
    return _default


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-default tracer (tests use this for isolation).
    Returns the previous tracer."""
    global _default
    with _default_lock:
        prev = _default
        _default = tracer
    return prev if prev is not None else tracer
