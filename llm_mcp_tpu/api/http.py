"""Zero-framework threaded HTTP layer: route table, JSON/SSE helpers.

The reference's API is a plain Go `http.ServeMux` with hand-rolled helpers
(`core/internal/api/helpers.go:11-43`) and its MCP bridge is zero-framework
`node:http` (`mcp/src/index.ts`). Same spirit here: stdlib
ThreadingHTTPServer, one thread per connection — which is exactly what
blocking-queue token streams from the engine want (no async bridging).
"""

from __future__ import annotations

import json
import logging
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable
from urllib.parse import parse_qs, urlparse

from ..telemetry import tracing
from ..utils.faults import maybe_fail

log = logging.getLogger("api")

MAX_BODY = 10 * 1024 * 1024  # 10MB cap, as the reference's chat handler


class Request:
    def __init__(self, handler: "_Handler", params: dict[str, str]):
        self._h = handler
        self.method = handler.command
        parsed = urlparse(handler.path)
        self.path = parsed.path
        self.query = {k: v[0] for k, v in parse_qs(parsed.query).items()}
        self.params = params  # path parameters, e.g. {id}
        self.headers = handler.headers
        self._body: bytes | None = None
        self.consumed = 0  # bytes of the body actually read

    def body(self) -> bytes:
        if self._body is None:
            length = int(self.headers.get("Content-Length") or 0)
            self._body = self._h.rfile.read(min(length, MAX_BODY)) if length else b""
            self.consumed = len(self._body)
        return self._body

    def json(self) -> Any:
        raw = self.body()
        if not raw:
            return {}
        return json.loads(raw)


class Response:
    """Write-side helper bound to one connection."""

    def __init__(self, handler: "_Handler"):
        self._h = handler
        self.started = False
        self.status = 0  # last status written (0 = nothing sent yet)
        # extra response headers (e.g. X-Selected-Model) emitted by every
        # write_* / start_sse below
        self.extra_headers: dict[str, str] = {}

    def _send_extra(self) -> None:
        for k, v in self.extra_headers.items():
            self._h.send_header(k, v)

    def write_json(self, obj: Any, status: int = 200) -> None:
        data = json.dumps(obj).encode("utf-8")
        h = self._h
        h.send_response(status)
        h.send_header("Content-Type", "application/json")
        h.send_header("Content-Length", str(len(data)))
        self._send_extra()
        h.end_headers()
        h.wfile.write(data)
        self.started = True
        self.status = status

    def write_error(self, message: str, status: int = 400, code: str = "") -> None:
        # error contract shape mirrors the reference (helpers_test.go:14-127)
        self.write_json({"error": {"message": message, "code": code or str(status)}}, status)

    def write_bytes(self, data: bytes, content_type: str, status: int = 200) -> None:
        h = self._h
        h.send_response(status)
        h.send_header("Content-Type", content_type)
        h.send_header("Content-Length", str(len(data)))
        self._send_extra()
        h.end_headers()
        h.wfile.write(data)
        self.started = True
        self.status = status

    # -- SSE ---------------------------------------------------------------

    def start_sse(self) -> None:
        h = self._h
        # No Content-Length: the stream ends when the server closes the
        # connection, so keep-alive must be off for this connection.
        h.close_connection = True
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.send_header("Cache-Control", "no-cache")
        h.send_header("X-Accel-Buffering", "no")
        self._send_extra()
        h.end_headers()
        self.started = True
        self.status = 200

    def sse_data(self, payload: Any) -> bool:
        """Send one `data:` frame; JSON-encodes non-strings. Returns False
        when the client disconnected."""
        if isinstance(payload, str):
            data = payload
        else:
            data = json.dumps(payload)
        try:
            self._h.wfile.write(f"data: {data}\n\n".encode("utf-8"))
            self._h.wfile.flush()
            return True
        except (BrokenPipeError, ConnectionResetError, OSError):
            return False

    def sse_event(self, event: str, payload: Any) -> bool:
        data = payload if isinstance(payload, str) else json.dumps(payload)
        try:
            self._h.wfile.write(f"event: {event}\ndata: {data}\n\n".encode("utf-8"))
            self._h.wfile.flush()
            return True
        except (BrokenPipeError, ConnectionResetError, OSError):
            return False


HandlerFn = Callable[[Request, Response], None]


class _Route:
    def __init__(self, method: str, pattern: str, fn: HandlerFn):
        self.method = method
        self.fn = fn
        # "/v1/jobs/{id}/stream" → regex with named groups
        regex = re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern)
        self.re = re.compile(f"^{regex}$")


class HTTPApi:
    def __init__(self):
        self._routes: list[_Route] = []
        self._server: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None

    def route(self, method: str, pattern: str, fn: HandlerFn) -> None:
        self._routes.append(_Route(method.upper(), pattern, fn))

    @staticmethod
    def _drain(handler: "_Handler", consumed: int) -> None:
        """Consume any unread request body so the next request on a
        keep-alive connection doesn't parse leftover bytes as its request
        line. Oversized bodies are not read — the connection closes."""
        try:
            length = int(handler.headers.get("Content-Length") or 0)
        except (TypeError, ValueError):
            length = 0
        remaining = length - consumed
        if remaining <= 0:
            return
        if remaining > MAX_BODY:
            handler.close_connection = True
            return
        try:
            handler.rfile.read(remaining)
        except OSError:
            handler.close_connection = True

    def dispatch(self, handler: "_Handler") -> None:
        path = urlparse(handler.path).path
        method = handler.command
        path_matched = False
        for r in self._routes:
            m = r.re.match(path)
            if not m:
                continue
            path_matched = True
            if r.method != method:
                continue
            req = Request(handler, m.groupdict())
            resp = Response(handler)
            tracer = tracing.get_tracer()
            # Root span per request, joining an inbound W3C traceparent when
            # present. Probe endpoints are untraced: /health and /metrics
            # polling would evict every interesting trace from the ring.
            trace = tracer.enabled and path not in tracing.UNTRACED_PATHS
            span = (
                tracer.start_span(
                    f"http {method} {path}",
                    parent=req.headers.get("traceparent") or tracing.NEW_TRACE,
                    attrs={"http.method": method, "http.path": path},
                )
                if trace
                else None
            )
            if span is not None:
                resp.extra_headers["X-Trace-Id"] = span.trace_id
                tracing.push_span(span)
            try:
                maybe_fail("api.request", path)
                r.fn(req, resp)
            except json.JSONDecodeError:
                if not resp.started:
                    resp.write_error("invalid JSON body", 400)
            except (BrokenPipeError, ConnectionResetError):
                handler.close_connection = True
                if span is not None:
                    span.set_error("client disconnected")
            except Exception as e:  # noqa: BLE001 — handler crash → 500
                log.exception("handler error %s %s", method, path)
                if span is not None:
                    span.set_error(f"{type(e).__name__}: {e}")
                if not resp.started:
                    resp.write_error(f"internal error: {e}", 500)
            finally:
                if span is not None:
                    tracing.pop_span(span)
                    if resp.status:
                        span.set_attr("http.status", resp.status)
                    span.end()
                self._drain(handler, req.consumed)
            return
        self._drain(handler, 0)
        resp = Response(handler)
        if path_matched:
            resp.write_error("method not allowed", 405)
        else:
            resp.write_error("not found", 404)

    # -- lifecycle -----------------------------------------------------------

    def serve(self, host: str, port: int) -> ThreadingHTTPServer:
        api = self

        class _Bound(_Handler):
            _api = api

        class _Server(ThreadingHTTPServer):
            daemon_threads = True
            # default backlog (5) drops/resets connections when many SSE
            # clients reconnect at once (64+ concurrent streams re-issuing
            # requests hit this in the serving benchmark)
            request_queue_size = 256

            def process_request_thread(self, request, client_address):
                # one line a handler thread in a profiler trace
                tracing.name_os_thread(f"http-{threading.get_native_id() % 100000}")
                super().process_request_thread(request, client_address)

        self._server = _Server((host, port), _Bound)
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="http-api", daemon=True
        )
        self._thread.start()
        return self._server

    @property
    def port(self) -> int:
        return self._server.server_address[1] if self._server else 0

    def shutdown(self) -> None:
        if self._server:
            self._server.shutdown()
            self._server.server_close()
            self._server = None


class _Handler(BaseHTTPRequestHandler):
    _api: HTTPApi
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt: str, *args: Any) -> None:
        log.debug("%s %s", self.address_string(), fmt % args)

    def _handle(self) -> None:
        self._api.dispatch(self)

    do_GET = _handle
    do_POST = _handle
    do_PUT = _handle
    do_DELETE = _handle
    do_PATCH = _handle
