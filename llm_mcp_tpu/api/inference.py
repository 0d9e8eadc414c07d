"""OpenAI-compatible sync inference surface: chat completions + embeddings,
and the smart-routed async `/v1/llm/request`.

Parity map (reference):
  - POST /v1/chat/completions: `core/internal/api/handlers.go:2087-2587` —
    but where the reference proxies Ollama NDJSON and re-chunks it into SSE
    (the token loop lives outside the repo), here the SSE frames come
    straight out of the in-process TPU decode loop.
  - POST /v1/embeddings: `handlers.go:1821-2078` — in-process encoder with
    exact Matryoshka `dimensions` truncation instead of the client-side
    fallback (`handlers.go:2063-2078`).
  - smart model selection when model=="" via model_rankings scoring:
    `handlers.go:2121-2159,3040-3144`.
  - POST /v1/llm/request: `handlers.go:645-697` — route, quality deadline,
    enqueue, 202.
  - `<think>` splitting into a reasoning field: `worker/llm_worker/main.py:207-219`.
  - cost + stats recording: `handlers.go:2608-2634,3147-3171`.

Remote TPU devices (another executor process found by discovery) are served
by proxying the same OpenAI-shaped request to the device's own HTTP address
— the analog of the reference's Ollama proxy hop, with circuit-breaker
bookkeeping on failures (`handlers.go:1899-1931`).
"""

from __future__ import annotations

import json
import logging
import os
import sqlite3
import time
import uuid
from typing import Any

from ..executor import EmbeddingEngine, GenerationEngine
from ..routing import Router, quality_deadline_s
from ..state.catalog import Catalog
from ..state.queue import JobQueue
from ..telemetry import Metrics, tracing
from ..utils.tokens import messages_to_prompt, split_think
from .http import Request, Response

log = logging.getLogger("inference")

CHAT_PROXY_TIMEOUT_S = 120.0
EMBED_PROXY_TIMEOUT_S = 120.0
EMBED_RETRIES = 3

# Tenancy (model zoo): the header whose value becomes GenRequest.tenant —
# per-tenant quotas, goodput ledgers and 429s all key off it. Operators
# fronting with an API-key gateway point TPU_TENANT_HEADER at their key
# header. Dynamic (read per request) so a live process can be re-keyed.
DEFAULT_TENANT_HEADER = "X-Tenant-Id"


def request_tenant(req: Request) -> str:
    """The request's tenant id, "" when the header is absent (unmetered —
    the single-tenant path touches none of the tenancy machinery)."""
    header = os.environ.get("TPU_TENANT_HEADER", "") or DEFAULT_TENANT_HEADER
    return (req.headers.get(header) or "").strip()


def parse_constraints(
    body: dict, n_vocab: int, bias_max: int
) -> tuple[dict | None, list | None, str | None]:
    """Distill the OpenAI-style structured-output surface into the engine's
    constraint spec: ``(constraint, logit_bias, error)``.

    - ``response_format``: ``json_object`` / ``json_schema`` (OpenAI), plus
      the ``regex`` and ``choice`` extensions (constrain/schema.py).
    - ``tools`` + ``tool_choice``: a FORCED tool call ("required" or a
      named function) becomes a json_schema constraint over the call
      object ``{"name": ..., "arguments": <parameters schema>}``;
      "auto"/"none"/absent leaves the model unconstrained.
    - ``logit_bias``: OpenAI token-id→bias map, values clamped to ±100;
      out-of-range ids and oversize maps are request errors (400), never
      silent truncation — a dropped bias entry would be an invisible
      behavior change.

    ``error`` is a 400-worthy message; both other slots are None then."""
    constraint: dict | None = None
    rf = body.get("response_format")
    if rf is not None:
        if not isinstance(rf, dict):
            return None, None, "response_format must be an object"
        typ = rf.get("type")
        if typ in (None, "text"):
            pass
        elif typ == "json_object":
            constraint = {"type": "json_object"}
        elif typ == "json_schema":
            js = rf.get("json_schema")
            schema = (
                js.get("schema") if isinstance(js, dict) else rf.get("schema")
            )
            if not isinstance(schema, (dict, bool)):
                return None, None, (
                    "response_format.json_schema requires a schema object"
                )
            constraint = {"type": "json_schema", "schema": schema}
        elif typ == "regex":
            pat = rf.get("pattern")
            if not isinstance(pat, str) or not pat:
                return None, None, "response_format.regex requires a pattern"
            constraint = {"type": "regex", "pattern": pat}
        elif typ == "choice":
            ch = rf.get("choices")
            if (
                not isinstance(ch, list)
                or not ch
                or not all(isinstance(c, str) and c for c in ch)
            ):
                return None, None, (
                    "response_format.choice requires non-empty string choices"
                )
            constraint = {"type": "choice", "choices": ch}
        else:
            return None, None, f"unsupported response_format type {typ!r}"
    tools = body.get("tools")
    tc = body.get("tool_choice")
    if tools is not None and tc not in (None, "none", "auto"):
        if not isinstance(tools, list) or not tools:
            return None, None, "tools must be a non-empty list"
        fns: dict[str, Any] = {}
        for t in tools:
            fn = t.get("function") if isinstance(t, dict) else None
            if not isinstance(fn, dict) or not fn.get("name"):
                return None, None, "each tool requires function.name"
            fns[str(fn["name"])] = fn.get("parameters")
        if isinstance(tc, dict):
            name = (tc.get("function") or {}).get("name")
            if name not in fns:
                return None, None, f"tool_choice names unknown tool {name!r}"
            fns = {name: fns[name]}
        elif tc != "required":
            return None, None, f"unsupported tool_choice {tc!r}"
        calls = [
            {
                "type": "object",
                "properties": {
                    "name": {"const": nm},
                    "arguments": params if params is not None else True,
                },
            }
            for nm, params in fns.items()
        ]
        constraint = {
            "type": "json_schema",
            "schema": calls[0] if len(calls) == 1 else {"anyOf": calls},
        }
    bias: list | None = None
    lb = body.get("logit_bias")
    if lb is not None:
        if not isinstance(lb, dict):
            return None, None, "logit_bias must map token ids to biases"
        if len(lb) > bias_max:
            return None, None, (
                f"logit_bias supports at most {bias_max} entries "
                "(LLM_MCP_TPU_CN_BIAS_MAX)"
            )
        bias = []
        for k, v in lb.items():
            try:
                tid, val = int(k), float(v)
            except (TypeError, ValueError):
                return None, None, f"invalid logit_bias entry {k!r}"
            if n_vocab and not (0 <= tid < n_vocab):
                return None, None, (
                    f"logit_bias token id {tid} out of range [0, {n_vocab})"
                )
            bias.append([tid, max(-100.0, min(100.0, val))])
    return constraint, bias, None


class InferenceAPI:
    def __init__(
        self,
        *,
        catalog: Catalog,
        queue: JobQueue,
        router: Router,
        metrics: Metrics,
        device_id: str = "tpu-local",
        gen_engines: dict[str, GenerationEngine] | None = None,
        embed_engines: dict[str, EmbeddingEngine] | None = None,
        cloud: Any = None,  # providers.CloudClient | None
        prefix_fetch: Any = None,  # CoreServer.maybe_prefix_fetch | None
        zoo: Any = None,  # executor.zoo.ModelZoo | None
    ):
        self.catalog = catalog
        self.queue = queue
        self.router = router
        self.metrics = metrics
        self.device_id = device_id
        self.gen_engines = gen_engines or {}
        self.embed_engines = embed_engines or {}
        self.cloud = cloud
        self.prefix_fetch = prefix_fetch
        self.zoo = zoo

    # -- helpers -----------------------------------------------------------

    def _local_gen(self, model: str) -> GenerationEngine | None:
        if model in self.gen_engines:
            return self.gen_engines[model]
        if self.zoo is not None and model in self.zoo.models():
            # zoo-managed model: resident engines return instantly; a
            # parked one pays its swap-in here, on the request thread —
            # the cold model's first token INCLUDES the swap
            try:
                return self.zoo.get(model)
            except (KeyError, RuntimeError):
                return None
        return None

    def _local_embed(self, model: str) -> EmbeddingEngine | None:
        return self.embed_engines.get(model)

    # accuracy level → (accuracy weight, cost factor), handlers.go:3051-3061
    _ACCURACY_WEIGHTS = {
        "low": (0.3, 3.0),
        "medium": (0.6, 1.5),
        "high": (0.9, 0.5),
        "critical": (1.0, 0.0),
    }

    def _select_model_smart(
        self,
        category: str = "general",
        accuracy: str = "medium",
        max_cost_usd: float = 0.0,
        messages: list | None = None,
        max_tokens: int = 512,
    ) -> str:
        """model=="" → best ranked model by category score × accuracy weight
        − cost factor × log-price tier (`handlers.go:3040-3144`): candidates
        failing the context fit or the caller's cost cap are skipped; a model
        unranked in the requested category falls back to its average score
        across categories, then to 50."""
        import math

        # estimated input tokens ≈ chars/4 (handlers.go:3042-3048)
        total_chars = 0
        for m in messages or []:
            c = m.get("content") if isinstance(m, dict) else None
            if isinstance(c, str):
                total_chars += len(c)
        est_tokens = total_chars / 4.0

        acc_weight, cost_factor = self._ACCURACY_WEIGHTS.get(
            accuracy, self._ACCURACY_WEIGHTS["medium"]
        )
        rows = self.catalog.db.query(
            """
            SELECT r.model_id,
                   MAX(CASE WHEN r.category = ? THEN r.score END) AS cat_score,
                   AVG(r.score) AS avg_score,
                   COALESCE(m.context_k, 0) AS context_k,
                   COALESCE(p.input_per_1m, 0) AS price_in,
                   COALESCE(p.output_per_1m, 0) AS price_out,
                   COALESCE(s.requests, 0) AS requests,
                   COALESCE(s.errors, 0) AS errors
            FROM model_rankings r
            LEFT JOIN models m ON m.id = r.model_id
            LEFT JOIN model_pricing p ON p.model_id = r.model_id
            LEFT JOIN model_stats s ON s.model_id = r.model_id
            GROUP BY r.model_id
            """,
            (category,),
        )
        best, best_score = "", -1e9
        for r in rows:
            ctx_k = r["context_k"] or 0
            if ctx_k > 0 and est_tokens > ctx_k * 1000:
                continue  # prompt won't fit the model's context
            # output side priced at the request's max_tokens (the reference
            # reuses the input estimate for both sides, handlers.go:3096 —
            # which underprices output-heavy requests by orders of magnitude)
            est_cost = (est_tokens / 1e6) * (r["price_in"] or 0) + (
                max(max_tokens, 0) / 1e6
            ) * (r["price_out"] or 0)
            if max_cost_usd > 0 and est_cost > max_cost_usd:
                continue
            cat_score = r["cat_score"]  # NULL (not 0.0) means unranked here
            if cat_score is None:
                cat_score = r["avg_score"] if r["avg_score"] is not None else 50.0
            # log-scaled input-price tier: cheap models win at low accuracy
            # regardless of prompt length (handlers.go:3115-3122)
            price_in = r["price_in"] or 0.0
            price_tier = math.log10(price_in * 1000 + 1) * 10 if price_in > 0 else 0.0
            # observed success rate multiplies the quality term — beyond the
            # reference formula: a model whose backend is failing most
            # requests must shed smart-routed traffic even if well ranked
            reqs = r["requests"] or 0
            success = (reqs - (r["errors"] or 0)) / reqs if reqs else 1.0
            score = cat_score * acc_weight * success - cost_factor * price_tier
            if score > best_score:
                best, best_score = r["model_id"], score
        if best:
            return best
        if rows:
            # ranked models existed but every one was filtered (context fit
            # or the caller's cost cap) — fail the selection like the
            # reference does ("no suitable model found",
            # handlers.go:3130-3132) rather than silently handing back a
            # model that violates the filters
            return ""
        # no rankings at all: any local llm from the catalog
        models = self.catalog.list_models(kind="llm")
        for m in models:
            if self._local_gen(m["id"]) is not None:
                return m["id"]
        return models[0]["id"] if models else ""

    # -- chat completions --------------------------------------------------

    def handle_chat_completions(self, req: Request, resp: Response) -> None:
        try:
            body = req.json()
        except json.JSONDecodeError:
            resp.write_error("invalid JSON body", 400)
            return
        model = str(body.get("model") or "")
        messages = body.get("messages") or []
        if not isinstance(messages, list) or not messages:
            resp.write_error("messages required", 400)
            return
        stream = bool(body.get("stream", False))
        try:
            raw_max = body.get("max_tokens", body.get("max_completion_tokens"))
            max_tokens = int(raw_max) if raw_max is not None else 512
            temperature = float(body.get("temperature", 0.7))
            top_p = float(body.get("top_p", 1.0))
        except (TypeError, ValueError) as e:
            resp.write_error(f"invalid numeric parameter: {e}", 400)
            return
        if max_tokens < 1:
            resp.write_error("max_tokens must be >= 1", 400)
            return
        stop = body.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]

        if not model:
            # smart selection surface: headers override body fields
            # (handlers.go:2122-2152); the chosen model is echoed back in
            # X-Selected-Model
            task_type = (
                req.headers.get("X-Task-Type")
                or str(body.get("task_type") or "")
                or "general"
            )
            accuracy = (
                req.headers.get("X-Accuracy")
                or str(body.get("accuracy") or "")
                or "medium"
            )
            try:
                max_cost = float(
                    req.headers.get("X-Max-Cost")
                    or body.get("max_cost_usd")
                    or 0.0
                )
            except (TypeError, ValueError):
                max_cost = 0.0
            model = self._select_model_smart(
                task_type, accuracy, max_cost, messages, max_tokens
            )
            if not model:
                resp.write_error("no model available", 503)
                return
            resp.extra_headers["X-Selected-Model"] = model
            # the proxy path forwards `body` — carry the selection so a
            # remote device serves exactly the advertised model instead of
            # re-selecting under its own defaults (handlers.go:2154-2159)
            body["model"] = model

        # slash names are the cloud namespace ("meta-llama/..." via
        # OpenRouter) — but only when no LOCAL engine carries the name: an
        # HF-style org/name id served from a local checkpoint dir
        # (models/configs.py:resolve_config) must not be shadowed by the
        # cloud heuristic
        if "/" in model and self._local_gen(model) is None:
            self._chat_cloud(req, resp, body, model, stream)
            return

        t0 = time.time()
        prompt = messages_to_prompt(messages)
        with tracing.get_tracer().span(
            "route", attrs={"model": model, "kind": "generate"}
        ) as rspan:
            engine = self._local_gen(model)
            dev = None if engine is not None else self.router.select_device(model, "generate")
            if engine is not None:
                rspan.set_attrs(
                    {"provider": "tpu", "device": self.device_id, "reason": "local-engine"}
                )
                # Fleet prefix tier: before dispatch, see whether this engine
                # (or a peer, via PrefixFetch) already holds the prompt's KV
                # prefix. Tokenizing here duplicates work the engine will do
                # at submit, but encode is microseconds against a prefill —
                # and it is what lets the route span carry the matched length.
                if self.prefix_fetch is not None:
                    outcome, matched = self.prefix_fetch(model, engine, prompt)
                    if outcome:
                        rspan.set_attr("prefix_matched_tokens", matched)
                        rspan.set_attr("prefix_outcome", outcome)
            else:
                rspan.set_attrs(
                    {
                        "provider": "tpu",
                        "device": dev["id"] if dev else "",
                        "reason": "device-select" if dev else "no-device",
                    }
                )
        if engine is None:
            if dev is not None and dev["id"] != self.device_id and dev["addr"]:
                self._chat_proxy(resp, dev, body, model, stream)
                return
            resp.write_error(f"model {model!r} not available on any device", 503)
            self.metrics.chat_requests.labels(model=model, provider="tpu", status="error").inc()
            return

        # Load shedding (executor/memory.py watermark + per-tenant quotas):
        # above the admission watermark, queueing more work only grows
        # every stream's latency — reject NOW with a drain estimate so
        # well-behaved clients back off (and the router's headroom tag
        # steers new traffic elsewhere). A request carrying a tenant id
        # also passes that tenant's token-bucket gate: an over-quota
        # tenant 429s HERE, per tenant, while in-quota tenants sail
        # through. admission_state is side-effect free; the shed is
        # recorded here, where the 429 actually happens. Embed engines
        # (and test stand-ins predating tenancy) lack the kwarg/method.
        tenant = request_tenant(req)
        adm = getattr(engine, "admission_state", None)
        if adm is None:
            shed, retry_after = False, 0.0
        elif tenant:
            try:
                shed, retry_after = adm(tenant=tenant)
            except TypeError:
                shed, retry_after = adm()
        else:
            shed, retry_after = adm()
        if shed:
            try:
                engine.note_shed(tenant=tenant)
            except TypeError:
                engine.note_shed()
            self.metrics.chat_requests.labels(
                model=model, provider="tpu", status="shed"
            ).inc()
            # (llmtpu_tenant_shed_total advances through the engines_info
            # delta bridge off the perf ledger note_shed just charged —
            # incrementing here too would double-count)
            resp.extra_headers["Retry-After"] = str(max(1, int(retry_after + 0.5)))
            resp.write_error(
                "server overloaded: admission watermark or tenant quota "
                "exceeded; retry after the indicated delay",
                429,
            )
            return

        try:
            priority = int(body.get("priority") or 0)
        except (TypeError, ValueError):
            priority = 0
        # structured-output surface: parsed AFTER engine resolution so the
        # vocab bound for logit_bias validation is the serving engine's
        cfg = getattr(engine, "cfg", None)
        constraint, logit_bias, cn_err = parse_constraints(
            body,
            int(getattr(cfg, "vocab_size", 0) or 0),
            int(getattr(engine, "cn_bias_max", 64)),
        )
        if cn_err is not None:
            resp.write_error(cn_err, 400)
            self.metrics.chat_requests.labels(
                model=model, provider="tpu", status="error"
            ).inc()
            return
        gen_kwargs = dict(
            max_tokens=max_tokens, temperature=temperature, top_p=top_p, stop=stop,
            priority=priority,
        )
        if tenant:
            # only metered requests carry the kwarg: the zero-tenant call
            # signature (and the GenRequest it builds) stays byte-identical
            gen_kwargs["tenant"] = tenant
        # same convention for constraints: unconstrained requests build a
        # byte-identical GenRequest
        if constraint is not None:
            gen_kwargs["constraint"] = constraint
        if logit_bias:
            gen_kwargs["logit_bias"] = logit_bias
        created = int(t0)
        cmpl_id = f"chatcmpl-{uuid.uuid4().hex[:24]}"

        if stream:
            self._chat_stream_local(resp, engine, model, prompt, gen_kwargs, cmpl_id, created, t0)
        else:
            self._chat_sync_local(resp, engine, model, prompt, gen_kwargs, cmpl_id, created, t0)

    def _chat_sync_local(self, resp, engine, model, prompt, gen_kwargs, cmpl_id, created, t0):
        with tracing.get_tracer().span("engine.generate", attrs={"model": model}) as sp:
            try:
                out = engine.generate(prompt, **gen_kwargs)
            except RuntimeError as e:
                sp.set_error(str(e))
                resp.write_error(str(e), 500)
                self.metrics.chat_requests.labels(model=model, provider="tpu", status="error").inc()
                self.router.circuit.record(self.device_id, ok=False)
                return
            sp.set_attrs(
                {
                    "prompt_tokens": out["usage"].get("prompt_tokens", 0),
                    "completion_tokens": out["usage"].get("completion_tokens", 0),
                    "finish_reason": out["finish_reason"],
                }
            )
        self.router.circuit.record(self.device_id, ok=True)
        usage = out["usage"]
        thinking, answer = split_think(out["text"])
        message: dict[str, Any] = {"role": "assistant", "content": answer}
        if thinking:
            message["reasoning"] = thinking
        resp.write_json(
            {
                "id": cmpl_id,
                "object": "chat.completion",
                "created": created,
                "model": model,
                "choices": [
                    {"index": 0, "message": message, "finish_reason": out["finish_reason"]}
                ],
                "usage": usage,
            }
        )
        self._record_chat(model, "tpu", usage, time.time() - t0, ok=True)

    def _chat_stream_local(self, resp, engine, model, prompt, gen_kwargs, cmpl_id, created, t0):
        resp.start_sse()
        base = {"id": cmpl_id, "object": "chat.completion.chunk", "created": created, "model": model}
        first = dict(base, choices=[{"index": 0, "delta": {"role": "assistant"}, "finish_reason": None}])
        if not resp.sse_data(first):
            return
        usage: dict[str, Any] = {}
        finish = "stop"
        ok = True
        ttft: float | None = None
        with tracing.get_tracer().span(
            "engine.generate", attrs={"model": model, "stream": True}
        ) as sp:
            for evt in engine.generate_stream(prompt, **gen_kwargs):
                if evt["type"] == "token":
                    if ttft is None:
                        ttft = time.time() - t0
                        self.metrics.chat_ttft.labels(model=model).observe(ttft)
                        sp.set_attr("ttft_ms", round(ttft * 1000.0, 1))
                    chunk = dict(
                        base,
                        choices=[{"index": 0, "delta": {"content": evt["text"]}, "finish_reason": None}],
                    )
                    if not resp.sse_data(chunk):
                        sp.set_attr("client_disconnected", True)
                        return  # client went away; engine keeps finishing the slot
                    if "t" in evt:  # the engine's stamp of its put
                        engine.observe_stream_write(evt["t"])
                elif evt["type"] == "done":
                    usage = evt.get("usage", {})
                    finish = evt.get("finish_reason", "stop")
                elif evt["type"] == "error":
                    ok = False
                    sp.set_error(evt.get("error", ""))
                    resp.sse_data(dict(base, error={"message": evt.get("error", "")}))
                    break
            sp.set_attrs(
                {
                    "prompt_tokens": usage.get("prompt_tokens", 0),
                    "completion_tokens": usage.get("completion_tokens", 0),
                    "finish_reason": finish,
                }
            )
        final = dict(
            base, choices=[{"index": 0, "delta": {}, "finish_reason": finish}], usage=usage
        )
        resp.sse_data(final)
        resp.sse_data("[DONE]")
        self.router.circuit.record(self.device_id, ok=ok)
        self._record_chat(model, "tpu", usage, time.time() - t0, ok=ok)

    def _chat_proxy(self, resp: Response, dev: dict, body: dict, model: str, stream: bool) -> None:
        """Forward to a remote TPU device's own /v1/chat/completions —
        the reference's Ollama-device hop (`handlers.go:2427-2470`)."""
        import httpx

        url = f"http://{dev['addr']}/v1/chat/completions"
        # carry the trace across the device hop (remote serves its own root
        # span joined to this trace via the traceparent header)
        ctx = tracing.current_traceparent()
        headers = {"traceparent": ctx} if ctx else {}
        try:
            if stream:
                with httpx.stream(
                    "POST", url, json=body, headers=headers, timeout=CHAT_PROXY_TIMEOUT_S
                ) as r:
                    if r.status_code >= 400:
                        # surface the remote error as an error, not a 200 SSE
                        r.read()
                        self.router.circuit.record(dev["id"], ok=r.status_code < 500)
                        resp.write_bytes(r.content, "application/json", r.status_code)
                        return
                    resp.start_sse()
                    for line in r.iter_lines():
                        if line.startswith("data: "):
                            if not resp.sse_data(line[len("data: "):]):
                                break
                self.router.circuit.record(dev["id"], ok=True)
            else:
                r = httpx.post(url, json=body, headers=headers, timeout=CHAT_PROXY_TIMEOUT_S)
                resp.write_bytes(r.content, "application/json", r.status_code)
                self.router.circuit.record(dev["id"], ok=r.status_code < 500)
        except Exception as e:  # connection-class failure → breaker
            self.router.circuit.record(dev["id"], ok=False)
            self.metrics.chat_requests.labels(model=model, provider="tpu", status="error").inc()
            if not resp.started:
                resp.write_error(f"device {dev['id']} unreachable: {e}", 502)

    def _chat_cloud(self, req: Request, resp: Response, body: dict, model: str, stream: bool) -> None:
        if self.cloud is None:
            resp.write_error("no cloud provider configured", 503)
            return
        t0 = time.time()
        sp = tracing.current_span()
        if sp is not None:
            sp.set_attrs({"provider": "cloud", "model": model})
        try:
            if stream:
                resp.start_sse()
                usage = {}
                for frame in self.cloud.chat_stream(body):
                    if isinstance(frame, dict):
                        usage = frame.get("usage") or usage
                    if not resp.sse_data(frame):
                        break
                resp.sse_data("[DONE]")
                self._record_chat(model, "cloud", usage, time.time() - t0, ok=True)
            else:
                out = self.cloud.chat(body)
                resp.write_json(out)
                self._record_chat(model, "cloud", out.get("usage", {}), time.time() - t0, ok=True)
        except Exception as e:
            self.metrics.chat_requests.labels(model=model, provider="cloud", status="error").inc()
            if not resp.started:
                resp.write_error(f"cloud provider error: {e}", 502)

    def _record_chat(self, model: str, provider: str, usage: dict, dt: float, ok: bool) -> None:
        status = "ok" if ok else "error"
        self.metrics.chat_requests.labels(model=model, provider=provider, status=status).inc()
        self.metrics.chat_duration.labels(model=model, provider=provider).observe(dt)
        tin = int(usage.get("prompt_tokens") or 0)
        tout = int(usage.get("completion_tokens") or 0)
        if tin:
            self.metrics.chat_tokens.labels(model=model, provider=provider, direction="in").inc(tin)
        if tout:
            self.metrics.chat_tokens.labels(model=model, provider=provider, direction="out").inc(tout)
        try:
            cost = self.catalog.record_cost(model, provider, tin, tout)
            if cost:
                self.metrics.chat_cost_usd.labels(model=model, provider=provider).inc(cost)
            self.catalog.update_model_stats(
                model, tokens_in=tin, tokens_out=tout, cost_usd=cost,
                duration_ms=dt * 1000.0, error=not ok,
            )
        except sqlite3.ProgrammingError:
            # server shutdown closed the DB while this handler's stream was
            # still finishing — the client already has its [DONE]; dropping
            # the post-hoc stats row beats crashing the handler
            log.debug("stats recording skipped: database closed mid-shutdown")

    # -- embeddings --------------------------------------------------------

    def handle_embeddings(self, req: Request, resp: Response) -> None:
        try:
            body = req.json()
        except json.JSONDecodeError:
            resp.write_error("invalid JSON body", 400)
            return
        model = str(body.get("model") or "")
        raw_input = body.get("input")
        if isinstance(raw_input, str):
            texts = [raw_input]
        elif isinstance(raw_input, list) and all(isinstance(t, str) for t in raw_input):
            texts = raw_input
        else:
            resp.write_error("input must be a string or list of strings", 400)
            return
        if not texts:
            resp.write_error("input must not be empty", 400)
            return
        try:
            dimensions = body.get("dimensions")
            dimensions = int(dimensions) if dimensions else None
        except (TypeError, ValueError):
            resp.write_error("dimensions must be an integer", 400)
            return

        if not model:
            embeds = self.catalog.list_models(kind="embed")
            local = [m["id"] for m in embeds if m["id"] in self.embed_engines]
            model = local[0] if local else (embeds[0]["id"] if embeds else "")
        if not model:
            resp.write_error("no embedding model available", 503)
            return

        # same local-first rule as chat: a slash name only means "cloud"
        # when no local embedding engine carries it
        if "/" in model and self._local_embed(model) is None:
            self._embed_cloud(resp, model, texts, dimensions)
            return

        t0 = time.time()
        engine = self._local_embed(model)
        if engine is not None:
            vectors, ntok = engine.embed(texts, dimensions=dimensions)
            self._write_embeddings(resp, model, vectors, ntok)
            self.metrics.embedding_requests.labels(
                model=model, device=self.device_id, status="ok"
            ).inc()
            self.metrics.embedding_duration.labels(model=model).observe(time.time() - t0)
            self.metrics.embedding_input_tokens.labels(model=model).inc(ntok)
            return

        # remote devices: ≤3 attempts across devices with breaker updates
        # (`handlers.go:1899-1931`)
        import httpx

        last_err = "no device has the model"
        for _ in range(EMBED_RETRIES):
            dev = self.router.select_device(model, "embed")
            if dev is None or dev["id"] == self.device_id or not dev["addr"]:
                break
            try:
                r = httpx.post(
                    f"http://{dev['addr']}/v1/embeddings",
                    json={"model": model, "input": texts, "dimensions": dimensions},
                    timeout=EMBED_PROXY_TIMEOUT_S,
                )
                r.raise_for_status()
                self.router.circuit.record(dev["id"], ok=True)
                resp.write_bytes(r.content, "application/json")
                self.metrics.embedding_requests.labels(
                    model=model, device=dev["id"], status="ok"
                ).inc()
                return
            except Exception as e:
                last_err = str(e)
                self.router.circuit.record(dev["id"], ok=False)
                self.metrics.embedding_requests.labels(
                    model=model, device=dev["id"], status="error"
                ).inc()
        resp.write_error(f"embeddings unavailable for {model!r}: {last_err}", 503)

    def _embed_cloud(self, resp: Response, model: str, texts: list[str], dimensions: int | None) -> None:
        if self.cloud is None:
            resp.write_error("no cloud provider configured", 503)
            return
        try:
            out = self.cloud.embed(model, texts, dimensions)
            # Matryoshka client-side truncation fallback (`handlers.go:2063-2078`)
            if dimensions and out.get("data"):
                for item in out["data"]:
                    vec = item.get("embedding") or []
                    if len(vec) > dimensions:
                        import math

                        vec = vec[:dimensions]
                        norm = math.sqrt(sum(v * v for v in vec)) or 1.0
                        item["embedding"] = [v / norm for v in vec]
            resp.write_json(out)
        except Exception as e:
            resp.write_error(f"cloud embeddings error: {e}", 502)

    @staticmethod
    def _write_embeddings(resp: Response, model: str, vectors: list[list[float]], ntok: int) -> None:
        resp.write_json(
            {
                "object": "list",
                "data": [
                    {"object": "embedding", "embedding": v, "index": i}
                    for i, v in enumerate(vectors)
                ],
                "model": model,
                "usage": {"prompt_tokens": ntok, "total_tokens": ntok},
            }
        )

    # -- async smart-routed request ---------------------------------------

    def handle_llm_request(self, req: Request, resp: Response) -> None:
        try:
            body = req.json()
        except json.JSONDecodeError:
            resp.write_error("invalid JSON body", 400)
            return
        kind = str(body.get("kind") or "generate")
        prompt = str(body.get("prompt") or "")
        if not prompt and body.get("messages"):
            prompt = messages_to_prompt(body["messages"])
        quality = str(body.get("quality") or "")
        thinking = body.get("thinking")
        decision = self.router.route(
            kind=kind,
            model=str(body.get("model") or ""),
            prompt=prompt,
            provider=str(body.get("provider") or "auto"),
            quality=quality,
            thinking=bool(thinking) if thinking is not None else None,
            max_latency_ms=float(body.get("max_latency_ms") or 0),
            force_cloud=bool(body.get("force_cloud", False)),
        )
        payload = dict(body)
        payload.update(decision.payload_overlay())
        # the job carries the trace context so queue-wait / worker / rpc
        # spans from other threads and processes join this request's trace
        ctx = tracing.current_traceparent()
        if ctx and "_traceparent" not in payload:
            payload["_traceparent"] = ctx
        deadline = None
        if quality:
            deadline = time.time() + quality_deadline_s(quality)
        job = self.queue.submit(kind, payload, deadline_at=deadline)
        self.metrics.jobs_created.labels(kind=kind).inc()
        sp = tracing.current_span()
        if sp is not None:
            sp.set_attrs({"job_id": job.id, "quality": quality or ""})
        resp.write_json(
            {
                "job_id": job.id,
                "provider": decision.provider,
                "kind": kind,
                "model": decision.model,
                "device_id": decision.device_id,
                "reason": decision.reason,
            },
            status=202,
        )
