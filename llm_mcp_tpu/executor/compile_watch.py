"""What JAX itself reports inside one first dispatch, by thread.

`jax.monitoring` fires, on the thread that dispatches, one duration event
each for tracing a function to a jaxpr, lowering the jaxpr to MLIR and the
backend's compile (which, with the persistent cache on, is either a compile
or a load from the cache), and one plain event for every compile request
that goes to the cache, for every hit and for every entry written. The
engine opens a context around a first dispatch (`begin` in
`_note_exec_shape`, `end` in `_compile_obs`); what fires on that thread in
between is summed into the dict `end` returns, which the CompileLedger
(telemetry/recorder.py, free of JAX) files with the dispatch's wall. With no
context open on the firing thread a listener does one attribute lookup.
"""

from __future__ import annotations

import threading
import time
from typing import Any

TRACE = "/jax/core/compile/jaxpr_trace_duration"
DURATIONS = {
    TRACE: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "compile_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
}

_tls = threading.local()
_register_lock = threading.Lock()
_registered = False


def _on_duration(event: str, duration_secs: float, **_: Any) -> None:
    ctx = getattr(_tls, "ctx", None)
    if ctx is None or event not in DURATIONS:
        return
    if event == TRACE:
        # a jitted function traced inside another reports first, and the
        # outer one's seconds hold it: keep outermost spans only
        end = time.monotonic()
        start = end - duration_secs
        ctx["traces"] = [t for t in ctx["traces"] if t[0] < start] + [(start, end)]
    else:
        ctx[DURATIONS[event]] += duration_secs


def _on_event(event: str, **_: Any) -> None:
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None and event in EVENTS:
        ctx[EVENTS[event]] += 1


def _register() -> None:
    global _registered
    with _register_lock:
        if not _registered:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _registered = True


def begin() -> None:
    """Open a context on this thread; one left open (its dispatch raised
    before `end`) is dropped."""
    if not _registered:
        _register()
    _tls.ctx = {**dict.fromkeys(DURATIONS.values(), 0.0),
                **dict.fromkeys(EVENTS.values(), 0), "traces": []}


def end() -> dict[str, Any] | None:
    """Close this thread's context: the ledger's COMPILE_PARTS and `hit`
    (every compile request was served from the persistent cache; None where
    JAX made none). None when no context was open."""
    ctx = getattr(_tls, "ctx", None)
    _tls.ctx = None
    if ctx is None:
        return None
    ctx["trace_s"] = sum(b - a for a, b in ctx.pop("traces"))
    requests, hits = ctx["compile_requests"], ctx.pop("cache_hits")
    ctx["hit"] = None if requests == 0 else hits >= requests
    return ctx
