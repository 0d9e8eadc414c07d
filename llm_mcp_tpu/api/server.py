"""Core API server: the single service wiring every surface together.

Parity: the reference's llmcore process (`core/cmd/core/main.go:26-123` boot,
`core/internal/api/server.go:32-62` route table — 27 HTTP routes). Layering
is the same (API → routing policy → state), but L1 execution is in-process:
the server can host TPU generation/embedding engines directly and registers
itself as a device in the catalog, so the routing brain sees it exactly like
any remote executor.

Route inventory (reference server.go:32-62 ↔ here):
  health, metrics, jobs CRUD + claim/complete/fail/heartbeat + SSE stream,
  llm/request, chat/completions, embeddings, models (+sync, +stats),
  devices (+offline), discovery/run, dashboard, costs (summary, balance),
  feedback, benchmarks, workers/register, debug (health, actions, capacity,
  test), knowledge/ingest.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any

from ..executor import EmbeddingEngine, GenerationEngine
from ..routing import CircuitBreaker, LimitsEngine, Router
from ..state.catalog import Catalog, sync_cloud_catalog
from ..state.db import Database
from ..state.queue import JobQueue
from ..telemetry import Metrics, tracing
from ..telemetry import recorder as flight
from ..telemetry import workload
from ..utils.config import Config
from .dashboard import DashboardAPI
from .http import HTTPApi, Request, Response
from .inference import InferenceAPI
from .jobs import JobsAPI
from .providers import CloudClient

log = logging.getLogger("server")

# span name → llmtpu_stage_duration_seconds stage label. rpc.* spans (any
# transport method) all observe under "rpc".
_SPAN_STAGES = {
    "queue.wait": "queue_wait",
    "route": "route",
    "engine.prefill": "prefill",
    "engine.decode": "decode",
    "engine.preempt": "preempt",
    "engine.restore": "restore",
    "engine.migrate_out": "migrate_out",
    "engine.migrate_in": "migrate_in",
}


class CoreServer:
    def __init__(
        self,
        cfg: Config | None = None,
        *,
        db: Database | None = None,
        gen_engines: dict[str, GenerationEngine] | None = None,
        embed_engines: dict[str, EmbeddingEngine] | None = None,
        device_id: str = "tpu-local",
        advertise_addr: str = "",
        zoo: Any = None,  # executor.zoo.ModelZoo | None (TPU_ZOO_MODELS boot)
    ):
        self.cfg = cfg or Config()
        self.db = db or Database(self.cfg.db_path)
        self.queue = JobQueue(self.db)
        self.catalog = Catalog(self.db)
        self.metrics = Metrics()
        # starved_rounds is cumulative per engine; the Prometheus counter
        # advances by the delta observed between engines_info() refreshes
        self._sched_starved: dict[str, float] = {}
        # same delta bookkeeping for the speculation token counters
        self._spec_counts: dict[str, dict[str, float]] = {}
        # and for the KV-pool preempt/restore/shed counters
        self._pool_counts: dict[str, dict[str, float]] = {}
        # and the paged-KV copy-on-write counter (cumulative per engine)
        self._paging_counts: dict[str, float] = {}
        # and the KV migration out/in/bytes counters (cumulative per engine)
        self._migration_counts: dict[str, dict[str, float]] = {}
        self._migration_requeues = 0.0
        # flight recorder / anomaly / watchdog bridges: events_total is
        # process-wide (one ring), anomaly dumps and watchdog transitions
        # are cumulative per engine+detector/state
        self._flight_events = 0.0
        self._anomaly_counts: dict[str, dict[str, float]] = {}
        self._watchdog_counts: dict[str, dict[str, float]] = {}
        # perf observatory: sampled phase walls are cumulative per
        # engine+phase+bucket, bridged by delta like the rest
        self._perf_phase_s: dict[str, dict[str, float]] = {}
        # per-tenant shed counts (perf tenant ledgers) bridge by delta to
        # llmtpu_tenant_shed_total{engine,tenant}; goodput gauges set direct
        self._tenant_shed: dict[str, dict[str, float]] = {}
        # latency waterfall (telemetry/workload.py): cumulative per-stage
        # seconds per engine, bridged by delta to
        # llmtpu_latency_stage_seconds{engine,stage}
        self._latency_stage_s: dict[str, dict[str, float]] = {}
        # fleet prefix tier (routing/prefix.py): engine export/import
        # counters bridge by delta; route outcomes accumulate here for the
        # dashboard/debug surfaces. prefix_sources lets in-process peers
        # (tests) register a duck-typed `prefix_fetch(ids)` source
        # directly; remote peers resolve lazily from their advertised
        # transfer_addr tag through a cached gRPC transfer client.
        self._prefix_tier_counts: dict[str, dict[str, float]] = {}
        self.prefix_sources: dict[str, Any] = {}
        self._prefix_clients: dict[str, Any] = {}
        self.transfer_addr = os.environ.get("TPU_TRANSFER_ADDR", "").strip()
        self._route_prefix = {
            "local": 0.0, "fetch": 0.0, "miss": 0.0,
            "fetch_fail": 0.0, "matched_tokens": 0.0, "fetch_ms": 0.0,
        }
        self._route_prefix_lock = threading.Lock()
        self.limits = LimitsEngine(self.db, strict=self.cfg.strict_model_limits)
        self.circuit = CircuitBreaker()
        self.router = Router(
            self.db,
            circuit=self.circuit,
            limits=self.limits,
            has_openrouter=self.cfg.has_openrouter(),
            has_openai=self.cfg.has_openai(),
        )
        self.cloud = (
            CloudClient(self.cfg)
            if (self.cfg.has_openrouter() or self.cfg.has_openai())
            else None
        )
        self.device_id = device_id
        self.advertise_addr = advertise_addr
        self.gen_engines = gen_engines or {}
        self.embed_engines = embed_engines or {}
        # Model zoo (executor/zoo.py): the router resolves quality tiers
        # resident-first through it, and the inference path swaps parked
        # models in on demand. None ⇒ single-model serving, no zoo code on
        # any path.
        self.zoo = zoo
        self.router.zoo = zoo

        self.inference = InferenceAPI(
            catalog=self.catalog,
            queue=self.queue,
            router=self.router,
            metrics=self.metrics,
            device_id=device_id,
            gen_engines=self.gen_engines,
            embed_engines=self.embed_engines,
            cloud=self.cloud,
            prefix_fetch=self.maybe_prefix_fetch,
            zoo=zoo,
        )
        self.jobs = JobsAPI(
            queue=self.queue,
            catalog=self.catalog,
            router=self.router,
            metrics=self.metrics,
            cfg=self.cfg,
            overload_check=self._jobs_overload_check,
        )
        self.dashboard = DashboardAPI(
            db=self.db,
            queue=self.queue,
            catalog=self.catalog,
            router=self.router,
            cfg=self.cfg,
            engines_info=self.engines_info,
            route_stats=self.route_prefix_stats,
            zoo_stats=lambda: (self.zoo.stats() if self.zoo is not None else None),
        )

        # Process-default tracer: the HTTP layer, router, engines, and
        # workers all land spans in this ring; /v1/traces serves it and the
        # observer below derives the per-stage latency histograms from it.
        self.tracer = tracing.get_tracer()
        self.tracer.add_observer(self._observe_span)

        self.api = HTTPApi()
        self._register_routes()
        self._bg_stop = threading.Event()
        self._bg_threads: list[threading.Thread] = []
        self._identity_cache: dict[str, Any] | None = None
        from ..discovery import Runner as DiscoveryRunner

        self.discovery = DiscoveryRunner(
            self.catalog,
            self.queue,
            limits=self.limits,
            cfg=self.cfg,
            register_local=self.register_local_device,
            self_device_id=device_id,
        )
        from ..planner import Planner

        self.planner = Planner(
            self.cfg,
            self.queue,
            self.catalog,
            cloud=self.cloud,
            gen_models=list(self.gen_engines),
            embed_models=list(self.embed_engines),
            device_id=device_id,
            gen_engines=self.gen_engines,
        )

        # KV migration (executor/migration.py). The coordinator only exists
        # when TPU_MIGRATE is on — with it off the engines never allocate
        # migration queues and none of the paths below run (true no-op).
        self.role = os.environ.get("TPU_ROLE", "both").strip().lower() or "both"
        self.migration = None
        if self.gen_engines and any(
            getattr(e, "_migrate_outbox", None) is not None
            for e in self.gen_engines.values()
        ):
            from ..executor.migration import MigrationCoordinator

            self.migration = MigrationCoordinator(
                self.gen_engines,
                role=self.role,
                drain_low=float(os.environ.get("TPU_MIGRATE_DRAIN_LOW", "0.25")),
                drain_high=float(os.environ.get("TPU_MIGRATE_DRAIN_HIGH", "0.5")),
                burst=int(os.environ.get("TPU_MIGRATE_BURST", "2")),
            )
            # TPU_MIGRATE_PEER=host:port[,host:port...] — remote decode-role
            # engines reachable over the KV transfer RPC (disaggregation
            # across processes). Lazy import: grpc stays optional.
            peers = os.environ.get("TPU_MIGRATE_PEER", "").strip()
            if peers:
                from ..rpc.client import RemoteMigrationTarget

                for addr in (p.strip() for p in peers.split(",")):
                    if addr:
                        self.migration.add_remote(addr, RemoteMigrationTarget(addr))

    # -- KV-pool admission bridge ------------------------------------------

    def _jobs_overload_check(self) -> tuple[bool, float]:
        """Worker claims defer while any local generation engine's KV pool
        is above the admission watermark — same signal as the 429 path on
        /v1/chat/completions, applied to the pull side of the queue. With
        no pool (TPU_KV_HOST_OFFLOAD=0), every engine reports (False, 0)
        and claims proceed untouched."""
        for e in self.gen_engines.values():
            shed, retry = getattr(e, "admission_state", lambda: (False, 0.0))()
            if shed:
                e.note_shed()
                if self.migration is not None:
                    # a shed is exactly the imbalance migration exists to
                    # fix — kick the drain tick instead of waiting it out
                    self.migration.note_pressure()
                return True, retry
        return False, 0.0

    def _kv_headroom_tag(self) -> float | None:
        """Min shed-free headroom across local pooled engines, or None when
        no engine runs a pool (tag omitted → router treats it as 1.0)."""
        vals = []
        for e in self.gen_engines.values():
            ms = getattr(e, "memory_stats", None)
            if ms is None:
                continue
            st = ms()
            if st.get("enabled"):
                vals.append(float(st.get("headroom", 1.0)))
        return min(vals) if vals else None

    def _prefill_cost_tag(self) -> float | None:
        """Measured prefill cost in µs/token across local engines — the
        perf observatory's prefill-family phase walls (admit / chunk /
        pf_rag) divided by the tokens they prefilled. None until enough
        sampled traffic exists; the router then uses its conservative
        default. This is the price side of the prefix-locality score:
        matched tokens × this cost = expected TTFT savings of a hit."""
        wall = tok = 0.0
        for e in self.gen_engines.values():
            pf = getattr(e, "perf_stats", None)
            if pf is None:
                continue
            phases = pf().get("phases", {})
            for p in ("admit", "chunk", "pf_rag"):
                r = phases.get(p) or {}
                wall += float(r.get("host_s", 0.0)) + float(r.get("device_s", 0.0))
                tok += float(r.get("tokens", 0.0))
        if tok <= 0 or wall <= 0:
            return None
        return wall / tok * 1e6

    def _prefix_digest_tag(self) -> dict | None:
        """Union digest of every local engine's resident prefix chains
        (routing/prefix.py merge_digests), or None when no engine caches
        prefixes — tag omitted, peers never score against this device."""
        from ..routing.prefix import merge_digests

        digests = []
        for e in self.gen_engines.values():
            pd = getattr(e, "prefix_digest", None)
            if pd is None:
                continue
            d = pd()
            if d:
                digests.append(d)
        return merge_digests(digests)

    # -- fleet prefix tier (routing/prefix.py; doc/performance.md) ---------

    def maybe_prefix_fetch(self, model: str, engine: Any, prompt: str) -> tuple[str, int]:
        """Serve-path hook (api/inference.py, before dispatch): does this
        engine — or a peer, via the PrefixFetch RPC — already hold the
        prompt's KV prefix? Returns (outcome, matched_tokens); outcome is
        "" when the tier is off or the engine has no prefix cache, else
        local | fetch | miss. A peer is only dialed when its advertised
        digest claims strictly more than the local cache AND at least
        TPU_PREFIX_FETCH_MIN_TOKENS — below that, recompute beats the wire
        (measured crossover; doc/performance.md). Fetch failures degrade
        to the local outcome: the prompt prefills from scratch exactly as
        it would have without the tier."""
        from ..routing import prefix as prefix_fp

        if not prefix_fp.prefix_route_enabled():
            return "", 0
        match_len = getattr(engine, "prefix_match_len", None)
        if match_len is None:
            return "", 0
        try:
            ids = [int(t) for t in engine.tokenizer.encode(prompt)]
        except Exception:
            return "", 0
        local = int(match_len(ids))
        outcome, matched = ("local", local) if local > 0 else ("miss", 0)
        best = self.router.best_prefix_peer(
            model,
            ids,
            exclude_device=self.device_id,
            min_tokens=max(prefix_fp.fetch_min_tokens(), local + 1),
        )
        if best is not None:
            dev, _claimed = best
            src = self._prefix_source_for(dev)
            if src is not None:
                t0 = time.time()
                payload = None
                try:
                    payload = src.prefix_fetch(ids)
                except ConnectionError as e:
                    log.warning("prefix fetch from %s failed: %s", dev.get("id"), e)
                    with self._route_prefix_lock:
                        self._route_prefix["fetch_fail"] += 1
                if payload and engine.prefix_import(payload):
                    matched = int(match_len(ids))
                    outcome = "fetch"
                    with self._route_prefix_lock:
                        self._route_prefix["fetch_ms"] += (time.time() - t0) * 1e3
        self.metrics.route_prefix_hit.labels(outcome=outcome).inc()
        self.metrics.route_prefix_matched_tokens.observe(matched)
        with self._route_prefix_lock:
            self._route_prefix[outcome] += 1
            self._route_prefix["matched_tokens"] += matched
        return outcome, matched

    def _prefix_source_for(self, dev: dict[str, Any]) -> Any:
        """Resolve a peer device row (router.best_prefix_peer, tags parsed)
        to something with `prefix_fetch(ids) -> bytes | None`."""
        src = self.prefix_sources.get(str(dev.get("id") or ""))
        if src is not None:
            return src
        addr = str((dev.get("tags") or {}).get("transfer_addr") or "").strip()
        if not addr:
            return None
        cli = self._prefix_clients.get(addr)
        if cli is None:
            try:
                from ..rpc.client import GrpcTransferClient

                cli = GrpcTransferClient(addr, timeout_s=30.0)
            except Exception:  # grpc not installed on this host
                return None
            self._prefix_clients[addr] = cli
        return cli

    def prefix_export(self, ids: list[int]) -> bytes | None:
        """PrefixFetch service callback (rpc/server.py KVTransferService):
        first local engine holding a resident chain for these prompt ids
        wins — single-model deployments have exactly one candidate."""
        for e in self.gen_engines.values():
            fn = getattr(e, "prefix_export", None)
            if fn is None:
                continue
            payload = fn(ids)
            if payload is not None:
                return payload
        return None

    def prefix_export_hash(self, hash16: str) -> bytes | None:
        """Hash-keyed PrefixFetch callback (boot-time peer warm-fill): the
        requester knows only the fleet digest's head hashes, not the token
        ids behind them — first local engine holding a resident chain whose
        digest head hash matches wins."""
        for e in self.gen_engines.values():
            fn = getattr(e, "prefix_export_by_hash", None)
            if fn is None:
                continue
            payload = fn(hash16)
            if payload is not None:
                return payload
        return None

    def route_prefix_stats(self) -> dict[str, float]:
        with self._route_prefix_lock:
            return dict(self._route_prefix)

    # -- cold start (executor/warmup.py; doc/performance.md) ---------------

    def boot_warmup(self) -> None:
        """Kick every local gen engine's warmup planner: the critical
        prefix (one admit bucket + one prefill executable + one decode
        shape) compiles synchronously here — start() calls this before
        device registration, so the first request never pays a cold XLA
        compile and the first advertisement already carries the warming
        tag — and the rest of the shape zoo fills in on the planner's
        background thread while serving."""
        priors = self._warmup_pack_priors()
        for e in self.gen_engines.values():
            fn = getattr(e, "start_warmup", None)
            if fn is None:
                continue
            try:
                fn(priors=priors)
            except Exception:
                log.exception("warmup planner failed to start")

    @staticmethod
    def _warmup_pack_priors() -> list[dict] | None:
        """Measured compile costs shipped with the compile cache: a warmup
        pack import (scripts/warmup_pack.py) drops warmup_plan.json next to
        the cache entries, and boot auto-loads it so the plan order
        reflects the exporting fleet's cost × hit aggregates even on a
        process with an empty local ledger."""
        from ..utils import config as ucfg

        cache_dir = ucfg.compile_cache_dir or ucfg.compile_cache_path()
        try:
            with open(os.path.join(cache_dir, "warmup_plan.json")) as f:
                rows = json.load(f)
        except (OSError, ValueError):
            return None
        return rows if isinstance(rows, list) else None

    def boot_prefix_warm(self, peers: int | None = None) -> int:
        """Peer warm-fill: pull the fleet's hottest resident prefix chains
        at boot. Head hashes are ranked by popularity across online peer
        devices' prefix_digest tags (peers holding the chain, then chain
        length), the top TPU_BOOT_PREFILL_PEERS of them are pulled through
        the hash-keyed PrefixFetch RPC, and the payloads import into the
        local engines — a joining engine serves its first shared-prefix
        request from fetched blocks instead of recomputing them. 0 (the
        default) disables. Returns the number of chains imported."""
        if peers is None:
            try:
                peers = int(os.environ.get("TPU_BOOT_PREFILL_PEERS", "0") or 0)
            except ValueError:
                peers = 0
        if peers <= 0 or not self.gen_engines:
            return 0
        heads: dict[str, dict[str, Any]] = {}
        for dev in self.catalog.list_devices(online_only=True):
            if str(dev.get("id")) == self.device_id:
                continue
            dig = (dev.get("tags") or {}).get("prefix_digest") or {}
            for h, toks in (dig.get("heads") or {}).items():
                ent = heads.setdefault(str(h), {"count": 0, "tokens": 0, "devs": []})
                ent["count"] += 1
                try:
                    ent["tokens"] = max(ent["tokens"], int(toks or 0))
                except (TypeError, ValueError):
                    pass
                ent["devs"].append(dev)
        ranked = sorted(
            heads.items(), key=lambda kv: (-kv[1]["count"], -kv[1]["tokens"], kv[0])
        )
        imported = 0
        for h, ent in ranked[: int(peers)]:
            payload = None
            for dev in ent["devs"]:
                src = self._prefix_source_for(dev)
                fetch = getattr(src, "prefix_fetch_hash", None)
                if fetch is None:
                    continue
                try:
                    payload = fetch(h)
                except ConnectionError as e:
                    log.warning(
                        "boot prefix fetch from %s failed: %s", dev.get("id"), e
                    )
                    payload = None
                if payload:
                    break
            if not payload:
                continue
            for e in self.gen_engines.values():
                imp = getattr(e, "prefix_import", None)
                try:
                    if imp is not None and imp(payload):
                        imported += 1
                        break
                except Exception:
                    log.exception("boot prefix import failed")
        if imported:
            log.info("boot prefix warm-fill: imported %d chain(s)", imported)
        return imported

    # -- local engine device registration ----------------------------------

    def register_local_device(self) -> None:
        """Advertise this process's engines as a schedulable device, with
        loaded models and slot capacity — the analog of discovery upserting
        an Ollama endpoint (`discovery.go:200-280`), self-registered."""
        models = list(self.gen_engines.keys()) + list(self.embed_engines.keys())
        if not models:
            return
        slots = sum(e.max_slots for e in self.gen_engines.values()) or 1
        import jax

        from ..utils.platform import device_platform

        platform = device_platform()
        n_chips = len(jax.devices())
        tags = {
            "tpu": platform == "tpu",
            "platform": platform,
            "chips": n_chips,
            "slots": slots,
            "self": True,
        }
        headroom = self._kv_headroom_tag()
        if headroom is not None:
            # router de-ranks saturated devices on this tag (router.py)
            tags["kv_headroom"] = round(headroom, 4)
        if self.role != "both":
            tags["role"] = self.role
        if self.migration is not None:
            # router prefers migration-capable devices among saturated
            # candidates (routing/router.py banding): a saturated device
            # that can drain itself recovers faster than one that sheds
            tags["migration"] = True
        if any(
            getattr(e, "warmup_stats", None) is not None
            and e.warmup_stats().get("state") != "fully_warm"
            for e in self.gen_engines.values()
        ):
            # warmup planner still compiling (executor/warmup.py): the
            # device serves, but router banding ranks it behind fully-warm
            # peers until its background compiles drain — a request routed
            # here may still hit an XLA compile stall.
            tags["warming"] = True
        # Prefix-locality routing inputs (routing/prefix.py + router.py):
        # the resident-chain digest, the live admission-queue depth, and
        # the measured prefill cost — refreshed on every discovery tick.
        # tags_at stamps the refresh so routing/limits.py can de-rank a
        # wedged device whose tags went stale (ROUTE_TAG_TTL_S).
        digest = self._prefix_digest_tag()
        if digest is not None:
            tags["prefix_digest"] = digest
        qd = sum(
            float(getattr(e, "queue_depth", lambda: 0)() or 0)
            for e in self.gen_engines.values()
        )
        tags["queue_depth"] = qd
        pc = self._prefill_cost_tag()
        if pc is not None:
            tags["prefill_us_per_tok"] = round(pc, 2)
        if self.transfer_addr:
            # peers dial this for PrefixFetch (and remote migration)
            tags["transfer_addr"] = self.transfer_addr
        tags["tags_at"] = time.time()
        self.catalog.upsert_device(
            self.device_id,
            name=self.device_id,
            addr=self.advertise_addr,
            online=True,
            tags=tags,
        )
        for m in self.gen_engines:
            self.catalog.upsert_model(m, kind="llm")
        for m in self.embed_engines:
            self.catalog.upsert_model(m, kind="embed")
        self.catalog.sync_device_models(self.device_id, models)

    def engines_info(self) -> dict[str, Any]:
        info: dict[str, Any] = {}
        engines = dict(self.gen_engines)
        if self.zoo is not None:
            # zoo residents that were swapped in after boot report like any
            # other engine; parked models are /v1/debug/zoo territory
            for name in self.zoo.resident_models():
                try:
                    engines.setdefault(name, self.zoo.get(name))
                except (KeyError, RuntimeError):
                    pass
        for name, e in engines.items():
            p50, p95, n = e.ttft_percentiles()
            info[name] = {
                "kind": "generate",
                "slots_in_use": e.slots_in_use(),
                "max_slots": e.max_slots,
                "total_tokens": e.total_tokens,
                "total_requests": e.total_requests,
                "total_errors": e.total_errors,
                "tps_10s": round(e.current_tps(), 1),
                "ttft_p50_ms": round(p50, 1),
                "ttft_p95_ms": round(p95, 1),
                "decode_compact": e.decode_compact,
                "stalled": e.stalled,
                "prefix_cache": e.prefix_cache_stats(),
                # engine-loop wall-clock by phase since boot (cumulative, so
                # operators can diff two dashboard snapshots)
                "phase_s": {
                    k: round(v, 1) for k, v in e.phase_budget().items()
                },
            }
            self.metrics.engine_slots_in_use.set(e.slots_in_use())
            self.metrics.engine_tps.set(e.current_tps())
            ss = getattr(e, "scheduler_stats", None)
            if ss is not None:
                st = ss()
                info[name]["scheduler"] = st
                self.metrics.sched_prefill_token_budget.set(
                    st.get("prefill_token_budget", 0.0)
                )
                self.metrics.sched_decode_occupancy.set(
                    st.get("decode_batch_occupancy", 0.0)
                )
                prev = self._sched_starved.get(name, 0.0)
                cur = float(st.get("starved_rounds", 0.0))
                if cur > prev:
                    self.metrics.sched_starved_rounds.inc(cur - prev)
                self._sched_starved[name] = cur
            sps = getattr(e, "speculation_stats", None)
            if sps is not None:
                sp = sps()
                info[name]["speculation"] = sp
                self.metrics.spec_accept_rate.labels(engine=name).set(
                    sp.get("accept_rate", 0.0)
                )
                self.metrics.spec_tok_per_call.labels(engine=name).set(
                    sp.get("tok_per_call", 0.0)
                )
                prev_c = self._spec_counts.get(name, {})
                for key, counter in (
                    ("drafted_tokens", self.metrics.spec_drafted_tokens),
                    ("emitted_tokens", self.metrics.spec_emitted_tokens),
                ):
                    cur_c = float(sp.get(key, 0.0))
                    if cur_c > prev_c.get(key, 0.0):
                        counter.labels(engine=name).inc(
                            cur_c - prev_c.get(key, 0.0)
                        )
                self._spec_counts[name] = {
                    "drafted_tokens": float(sp.get("drafted_tokens", 0.0)),
                    "emitted_tokens": float(sp.get("emitted_tokens", 0.0)),
                }
            mst = getattr(e, "memory_stats", None)
            if mst is not None:
                ms = mst()
                if ms.get("enabled"):
                    info[name]["memory"] = ms
                    self.metrics.kv_pool_headroom.labels(engine=name).set(
                        ms.get("headroom", 1.0)
                    )
                    prev_p = self._pool_counts.get(name, {})
                    for key, counter in (
                        ("preempted_total", self.metrics.kv_preempted),
                        ("restored_total", self.metrics.kv_restored),
                        ("shed_total", self.metrics.kv_shed),
                    ):
                        cur_p = float(ms.get(key, 0.0))
                        if cur_p > prev_p.get(key, 0.0):
                            counter.labels(engine=name).inc(
                                cur_p - prev_p.get(key, 0.0)
                            )
                    self._pool_counts[name] = {
                        k: float(ms.get(k, 0.0))
                        for k in ("preempted_total", "restored_total", "shed_total")
                    }
            pst = getattr(e, "paging_stats", None)
            if pst is not None:
                ps = pst()
                info[name]["paging"] = ps
                self.metrics.kv_blocks_used.labels(engine=name).set(
                    ps.get("blocks_used", 0.0)
                )
                self.metrics.kv_block_sharing.labels(engine=name).set(
                    ps.get("sharing_ratio", 1.0)
                )
                self.metrics.kv_block_leaks.labels(engine=name).set(
                    ps.get("leaks", 0.0)
                )
                prev_b = self._paging_counts.get(name, 0.0)
                cur_b = float(ps.get("cow_copies_total", 0.0))
                if cur_b > prev_b:
                    self.metrics.kv_cow_copies.labels(engine=name).inc(
                        cur_b - prev_b
                    )
                self._paging_counts[name] = cur_b
            mgs = getattr(e, "migration_stats", None)
            if mgs is not None:
                mg = mgs()
                if mg.get("enabled"):
                    info[name]["migration"] = mg
                    prev_m = self._migration_counts.get(name, {})
                    for key, counter in (
                        ("migrated_out_total", self.metrics.kv_migrated_out),
                        ("migrated_in_total", self.metrics.kv_migrated_in),
                        ("migrate_out_bytes_total", self.metrics.kv_migrate_bytes),
                    ):
                        cur_m = float(mg.get(key, 0.0))
                        if cur_m > prev_m.get(key, 0.0):
                            counter.labels(engine=name).inc(
                                cur_m - prev_m.get(key, 0.0)
                            )
                    self._migration_counts[name] = {
                        k: float(mg.get(k, 0.0))
                        for k in (
                            "migrated_out_total",
                            "migrated_in_total",
                            "migrate_out_bytes_total",
                        )
                    }
            pts = getattr(e, "prefix_tier_stats", None)
            if pts is not None:
                pt = pts()
                if pt.get("enabled"):
                    info[name]["prefix_tier"] = pt
                    prev_t = self._prefix_tier_counts.get(name, {})
                    for key, counter in (
                        ("exports_total", self.metrics.prefix_tier_exports.labels(engine=name)),
                        ("imports_total", self.metrics.prefix_tier_imports.labels(engine=name)),
                        ("import_rejects_total", self.metrics.prefix_tier_rejects.labels(engine=name)),
                        ("export_bytes_total", self.metrics.prefix_tier_bytes.labels(engine=name, direction="out")),
                        ("import_bytes_total", self.metrics.prefix_tier_bytes.labels(engine=name, direction="in")),
                    ):
                        cur_t = float(pt.get(key, 0.0))
                        if cur_t > prev_t.get(key, 0.0):
                            counter.inc(cur_t - prev_t.get(key, 0.0))
                    self._prefix_tier_counts[name] = {
                        k: float(pt.get(k, 0.0))
                        for k in (
                            "exports_total",
                            "imports_total",
                            "import_rejects_total",
                            "export_bytes_total",
                            "import_bytes_total",
                        )
                    }
            pfs = getattr(e, "perf_stats", None)
            if pfs is not None:
                pf = pfs()
                info[name]["perf"] = pf
                gp = pf.get("goodput") or {}
                rl = pf.get("roofline") or {}
                self.metrics.goodput_tok_per_s.labels(engine=name).set(
                    gp.get("goodput_tok_per_s", 0.0)
                )
                self.metrics.goodput_ratio.labels(engine=name).set(
                    gp.get("goodput_ratio", 1.0)
                )
                if "decode_mfu" in rl:
                    # present only where the device kind has published
                    # peaks (telemetry/perf.py CHIP_PEAKS): no gauge on CPU
                    self.metrics.decode_mfu.labels(engine=name).set(
                        rl["decode_mfu"]
                    )
                    self.metrics.decode_mbu.labels(engine=name).set(
                        rl["decode_mbu"]
                    )
                # per-tenant goodput (model zoo tenancy): gauges set
                # direct; shed counts advance by delta like every other
                # cumulative bridge. No tenants ⇒ empty dict ⇒ no series.
                tns = pf.get("tenants") or {}
                prev_ts = self._tenant_shed.get(name, {})
                cur_ts: dict[str, float] = {}
                for tenant, tgp in tns.items():
                    self.metrics.goodput_tok_per_s_tenant.labels(
                        engine=name, tenant=tenant
                    ).set(tgp.get("goodput_tok_per_s", 0.0))
                    self.metrics.goodput_ratio_tenant.labels(
                        engine=name, tenant=tenant
                    ).set(tgp.get("goodput_ratio", 1.0))
                    cur_shed = float(tgp.get("shed", 0.0))
                    cur_ts[tenant] = cur_shed
                    if cur_shed > prev_ts.get(tenant, 0.0):
                        self.metrics.tenant_shed_total.labels(
                            engine=name, tenant=tenant
                        ).inc(cur_shed - prev_ts.get(tenant, 0.0))
                self._tenant_shed[name] = cur_ts
                # sampled phase walls advance by delta, per (phase, bucket)
                prev_ph = self._perf_phase_s.get(name, {})
                cur_ph: dict[str, float] = {}
                for ph, rec_ in (pf.get("phases") or {}).items():
                    for bucket in ("host_s", "device_s", "wait_s"):
                        k = f"{ph}/{bucket}"
                        cur = float(rec_.get(bucket, 0.0))
                        cur_ph[k] = cur
                        if cur > prev_ph.get(k, 0.0):
                            self.metrics.perf_phase_seconds.labels(
                                engine=name, phase=ph,
                                bucket=bucket[:-2],
                            ).inc(cur - prev_ph.get(k, 0.0))
                self._perf_phase_s[name] = cur_ph
                # each ITL sample lands in the histogram exactly once
                drain = getattr(e, "drain_itl_samples", None)
                if drain is not None:
                    h = self.metrics.itl_seconds.labels(engine=name)
                    for v in drain():
                        h.observe(v)
            fst = getattr(e, "flight_stats", None)
            if fst is not None:
                fs = fst()
                info[name]["flight"] = fs
                by_det = (fs.get("anomaly") or {}).get("by_detector") or {}
                prev_a = self._anomaly_counts.get(name, {})
                for det, cur_a in by_det.items():
                    if float(cur_a) > prev_a.get(det, 0.0):
                        self.metrics.anomaly_dumps.labels(
                            engine=name, detector=det
                        ).inc(float(cur_a) - prev_a.get(det, 0.0))
                self._anomaly_counts[name] = {
                    det: float(v) for det, v in by_det.items()
                }
                wts = fs.get("watchdog_transitions") or {}
                prev_w = self._watchdog_counts.get(name, {})
                for state, cur_w in wts.items():
                    if float(cur_w) > prev_w.get(state, 0.0):
                        self.metrics.watchdog_transitions.labels(
                            engine=name, state=state
                        ).inc(float(cur_w) - prev_w.get(state, 0.0))
                self._watchdog_counts[name] = {
                    state: float(v) for state, v in wts.items()
                }
            wfs = getattr(e, "waterfall_stats", None)
            if wfs is not None:
                w = wfs()
                info[name]["waterfall"] = w
                # per-request stage walls are cumulative per engine+stage;
                # the counter advances by the delta between refreshes
                prev_l = self._latency_stage_s.get(name, {})
                cur_l: dict[str, float] = {}
                for stage, cur in (w.get("stage_s") or {}).items():
                    cur = float(cur)
                    cur_l[stage] = cur
                    if cur > prev_l.get(stage, 0.0):
                        self.metrics.latency_stage_seconds.labels(
                            engine=name, stage=stage
                        ).inc(cur - prev_l.get(stage, 0.0))
                self._latency_stage_s[name] = cur_l
            wls = getattr(e, "workload_stats", None)
            if wls is not None:
                info[name]["workload"] = wls()
        # Process-wide flight ring + compile ledger (telemetry/recorder.py
        # singletons shared by every engine in this process): events advance
        # by delta, drops are a gauge (nonzero: a dump lost events), and each
        # fresh ledger entry feeds the compile histogram exactly once.
        rec = flight.get_recorder()
        cur_ev = float(rec.events_total())
        if cur_ev > self._flight_events:
            self.metrics.flight_events.inc(cur_ev - self._flight_events)
            self._flight_events = cur_ev
        self.metrics.flight_dropped.set(float(rec.dropped_events))
        for entry in flight.get_compile_ledger().drain_fresh():
            self.metrics.compile_seconds.labels(
                engine=self.device_id,
                phase=entry["phase"],
                hit={True: "hit", False: "miss"}.get(entry["hit"], "unknown"),
            ).observe(float(entry["wall_s"]))
        if self.migration is not None:
            cst = self.migration.stats()
            self.metrics.kv_migration_headroom_delta.set(
                cst.get("headroom_delta", 0.0)
            )
            cur_r = float(cst.get("requeues_total", 0.0))
            if cur_r > self._migration_requeues:
                self.metrics.kv_migrate_requeues.inc(cur_r - self._migration_requeues)
                self._migration_requeues = cur_r
        for name, e in self.embed_engines.items():
            st = e.stats(recent=False)
            info[name] = {
                "kind": "embed",
                "total_inputs": e.total_inputs,
                "total_tokens": e.total_tokens,
                # forwards (of them `ahead`: dispatched behind one not yet
                # ready), texts, rows packed and dispatched, tokens true and
                # padded, seconds waiting for the lock, holding it (staging
                # and dispatch) and blocked in a fetch, `inflight_max`
                **st,
                # texts a row (1.0 = packing never engaged) and the share of
                # the dispatched token positions that were padding
                "texts_per_row": st["rows"] / st["rows_packed"] if st["rows_packed"] else 0.0,
                "pad_waste_pct": (
                    100.0 * (1.0 - st["true_tokens"] / st["padded_tokens"])
                    if st["padded_tokens"] else 0.0
                ),
            }
        return info

    # -- routes ------------------------------------------------------------

    def _register_routes(self) -> None:
        r = self.api.route
        r("GET", "/health", self.handle_health)
        r("GET", "/metrics", self.handle_metrics)

        # jobs + worker protocol
        r("POST", "/v1/jobs", self.jobs.handle_submit)
        r("GET", "/v1/jobs", self.jobs.handle_list)
        r("GET", "/v1/jobs/{id}", self.jobs.handle_get)
        r("DELETE", "/v1/jobs/{id}", self.jobs.handle_cancel)
        r("GET", "/v1/jobs/{id}/stream", self.jobs.handle_stream)
        r("POST", "/v1/jobs/claim", self.jobs.handle_claim)
        r("POST", "/v1/jobs/{id}/complete", self.jobs.handle_complete)
        r("POST", "/v1/jobs/{id}/fail", self.jobs.handle_fail)
        r("POST", "/v1/jobs/{id}/heartbeat", self.jobs.handle_heartbeat)
        r("POST", "/v1/workers/register", self.jobs.handle_worker_register)
        r("POST", "/v1/devices/offline", self.jobs.handle_devices_offline)

        # inference
        r("POST", "/v1/llm/request", self.inference.handle_llm_request)
        r("POST", "/v1/chat/completions", self.inference.handle_chat_completions)
        r("POST", "/v1/embeddings", self.inference.handle_embeddings)

        # catalog
        r("GET", "/v1/models", self.handle_models)
        r("POST", "/v1/models/sync", self.handle_models_sync)
        r("GET", "/v1/models/stats", self.handle_model_stats)
        r("GET", "/v1/devices", self.handle_devices)
        r("GET", "/v1/benchmarks", self.handle_benchmarks)

        # discovery
        r("POST", "/v1/discovery/run", self.handle_discovery_run)

        # observability / business
        r("GET", "/v1/traces", self.handle_traces)
        r("GET", "/v1/traces/{id}", self.handle_trace)
        r("GET", "/v1/dashboard", self.dashboard.handle_dashboard)
        r("GET", "/v1/costs/summary", self.handle_costs_summary)
        r("GET", "/v1/costs/balance", self.handle_costs_balance)
        r("POST", "/v1/feedback", self.handle_feedback)
        r("GET", "/v1/debug/health", self.dashboard.handle_health)
        r("GET", "/v1/debug/actions", self.dashboard.handle_actions)
        r("GET", "/v1/debug/capacity", self.dashboard.handle_capacity)
        r("POST", "/v1/debug/test", self.dashboard.handle_smoke_test)
        r("GET", "/v1/debug/flight", self.handle_debug_flight)
        r("GET", "/v1/debug/compiles", self.handle_debug_compiles)
        r("GET", "/v1/debug/warmup", self.handle_debug_warmup)
        r("GET", "/v1/debug/perf", self.handle_debug_perf)
        r("GET", "/v1/debug/zoo", self.handle_debug_zoo)
        r("GET", "/v1/debug/workload", self.handle_debug_workload)
        r("GET", "/v1/debug/constrain", self.handle_debug_constrain)
        r("GET", "/v1/debug/latency", self.handle_debug_latency)
        r("GET", "/v1/debug/prefix", self.handle_debug_prefix)
        r("GET", "/v1/debug/profile", self.handle_debug_profile)
        r("POST", "/v1/debug/profile", self.handle_debug_profile_start)

        # knowledge
        r("POST", "/v1/knowledge/ingest", self.handle_knowledge_ingest)

        # planner (manual trigger + status; periodic runs via _ticker)
        r("POST", "/v1/planner/run", self.handle_planner_run)
        r("GET", "/v1/planner/status", self.handle_planner_status)

    # -- small handlers ------------------------------------------------------

    def handle_health(self, req: Request, resp: Response) -> None:
        # Executor identity fields feed peer discovery: probes read platform/
        # chips/hbm_gb to tag the device and derive its limits (the analog of
        # the reference deriving limits from reported RAM, limits.go:124-160).
        # The prefix tier's dynamic fields ride along so HTTP-discovered
        # peers can score prefix locality and boot-warm from this device
        # (discovery copies them into the catalog tags): the resident-chain
        # digest and the gRPC address PrefixFetch answers on.
        body = {"status": "ok", "service": "llm-mcp-tpu", **self._device_identity()}
        digest = self._prefix_digest_tag()
        if digest:
            body["prefix_digest"] = digest
        if self.transfer_addr:
            body["transfer_addr"] = self.transfer_addr
        resp.write_json(body)

    def _device_identity(self) -> dict[str, Any]:
        # Platform/chips/HBM are static for the life of the process, and
        # /health is the hot probe target (peer discovery, subnet sweeps,
        # LB checks) — compute once.
        if self._identity_cache is not None:
            return self._identity_cache
        ident: dict[str, Any] = {"device_id": self.device_id}
        if self.gen_engines or self.embed_engines:
            # a process with engines has a device; one without (proxy-only
            # core) never imports jax
            import jax

            from ..utils.platform import device_platform

            devs = jax.devices()
            ident["platform"] = device_platform()
            ident["chips"] = len(devs)
            stats = devs[0].memory_stats()  # None on the CPU backend
            if stats and "bytes_limit" in stats:
                ident["hbm_gb"] = round(
                    len(devs) * stats["bytes_limit"] / (1 << 30), 1
                )
        ident["engines"] = sorted(list(self.gen_engines) + list(self.embed_engines))
        self._identity_cache = ident
        return ident

    def handle_metrics(self, req: Request, resp: Response) -> None:
        self.engines_info()  # refresh engine slot/tps gauges at scrape time
        self.metrics.devices_online.set(
            len(self.catalog.list_devices(online_only=True))
        )
        data, ctype = self.metrics.render()
        resp.write_bytes(data, ctype)

    def _observe_span(self, span: tracing.Span) -> None:
        """Tracer observer → per-stage latency histograms. Keeps the span
        library metrics-free: the bridge lives here."""
        stage = _SPAN_STAGES.get(span.name) or (
            "rpc" if span.name.startswith("rpc.") else ""
        )
        if stage:
            self.metrics.stage_duration.labels(stage=stage).observe(span.duration_s)

    def handle_traces(self, req: Request, resp: Response) -> None:
        """Newest-first summaries of the completed-trace ring."""
        try:
            limit = int(req.query.get("limit") or 50)
        except ValueError:
            resp.write_error("limit must be an integer", 400)
            return
        resp.write_json(
            {"enabled": self.tracer.enabled, "traces": self.tracer.traces(limit=limit)}
        )

    def handle_trace(self, req: Request, resp: Response) -> None:
        trace_id = req.params["id"]
        spans = self.tracer.get_trace(trace_id)
        if not spans:
            resp.write_error("trace not found", 404)
            return
        resp.write_json({"trace_id": trace_id, "spans": spans})

    # -- flight recorder / compile ledger / profiler (doc/observability.md) --

    def handle_debug_flight(self, req: Request, resp: Response) -> None:
        """Live tail of the flight-recorder ring plus anomaly-dump history.
        `?limit=N` bounds the event tail, `?etype=X` filters by event type,
        `?dump=1` forces a journal dump (rate-limit bypassed) — the manual
        equivalent of an anomaly trigger, for capturing a healthy baseline."""
        try:
            limit = int(req.query.get("limit") or 100)
        except ValueError:
            resp.write_error("limit must be an integer", 400)
            return
        rec = flight.get_recorder()
        out: dict[str, Any] = {
            "recorder": rec.stats(),
            "events": rec.snapshot(limit=limit, etype=req.query.get("etype") or ""),
            "anomalies": {
                name: e.anomaly_history()
                for name, e in self.gen_engines.items()
                if getattr(e, "anomaly_history", None) is not None
            },
        }
        if req.query.get("dump") in ("1", "true", "yes"):
            out["dump_path"] = rec.dump("manual", detector="api", force=True)
        resp.write_json(out)

    def handle_debug_compiles(self, req: Request, resp: Response) -> None:
        """Queryable compile ledger: per-shape aggregates (costliest first)
        and the raw first-sighting entries behind llmtpu_compile_seconds."""
        try:
            limit = int(req.query.get("limit") or 100)
        except ValueError:
            resp.write_error("limit must be an integer", 400)
            return
        led = flight.get_compile_ledger()
        resp.write_json(
            {
                "stats": led.stats(),
                "table": led.table(),
                "entries": led.entries(limit=limit),
            }
        )

    def handle_debug_warmup(self, req: Request, resp: Response) -> None:
        """Warmup readiness per engine (executor/warmup.py): planner state
        (cold / first_token_ready / fully_warm), per-step plan status, and
        background-compile progress — plus the boot-time peer warm-fill
        outcome."""
        resp.write_json(
            {
                "engines": {
                    name: e.warmup_stats()
                    for name, e in self.gen_engines.items()
                    if getattr(e, "warmup_stats", None) is not None
                },
                "boot_prefix_imported": getattr(self, "_boot_prefix_imported", 0),
            }
        )

    def handle_debug_perf(self, req: Request, resp: Response) -> None:
        """Perf observatory (telemetry/perf.py) per engine: ITL/TPOT
        percentiles, the goodput split against the TTFT+ITL SLO — both
        engine-wide and per tenant ("tenants": goodput + shed counts per
        tenant id) — sampled per-phase {host, device, wait} attribution
        (TPU_PERF_SAMPLE), and the four-layout roofline (MFU/MBU vs
        the device kind's published peaks)."""
        engines = dict(self.gen_engines)
        if self.zoo is not None:
            for name in self.zoo.resident_models():
                try:
                    engines.setdefault(name, self.zoo.get(name))
                except (KeyError, RuntimeError):
                    pass
        out = {
            name: e.perf_stats()
            for name, e in engines.items()
            if getattr(e, "perf_stats", None) is not None
        }
        # per-tenant quota state (scheduler token buckets) joins each
        # engine's document so one fetch answers "who is being throttled
        # and why" — ledger (finished) and bucket (admission) side by side
        for name, e in engines.items():
            ss = getattr(e, "scheduler_tenant_stats", None)
            if ss is not None and name in out:
                out[name]["tenant_quotas"] = ss()
        resp.write_json(out)

    def handle_debug_zoo(self, req: Request, resp: Response) -> None:
        """Model zoo residency (executor/zoo.py): per-model
        resident/parked state, the HBM partition (weight bytes from the
        zoo census, KV bytes from each resident engine's pool), swap
        counters and last swap latencies. `{"enabled": false}` when no
        zoo is configured (TPU_ZOO_MODELS unset)."""
        if self.zoo is None:
            resp.write_json({"enabled": False})
            return
        st = self.zoo.stats()
        st["enabled"] = True
        resp.write_json(st)

    def handle_debug_workload(self, req: Request, resp: Response) -> None:
        """Workload capture (telemetry/workload.py): the process-shared
        ring's health plus its newest records. `?limit=N` bounds the record
        tail; `?dump=PATH` journals the whole ring to PATH as replayable
        JSONL (the manual equivalent of streaming via TPU_WORKLOAD_TRACE)."""
        try:
            limit = int(req.query.get("limit") or 100)
        except ValueError:
            resp.write_error("limit must be an integer", 400)
            return
        wl = workload.get_workload()
        out: dict[str, Any] = {
            "workload": wl.stats(),
            "records": wl.snapshot(limit=limit),
        }
        dump_path = (req.query.get("dump") or "").strip()
        if dump_path:
            try:
                out["dumped"] = wl.dump(dump_path)
                out["dump_path"] = dump_path
            except OSError as e:
                resp.write_error(f"dump failed: {e}", 400)
                return
        resp.write_json(out)

    def handle_debug_constrain(self, req: Request, resp: Response) -> None:
        """Grammar-constrained decoding (llm_mcp_tpu/constrain) per engine:
        kill-switch state (TPU_CONSTRAIN), request/token/illegal counters,
        schema validity, host mask cost per token, the spec-composition
        accept rate, and the compile cache's hit/miss/eviction + mask-memo
        stats (TPU_CONSTRAIN_CACHE)."""
        engines = dict(self.gen_engines)
        if self.zoo is not None:
            for name in self.zoo.resident_models():
                try:
                    engines.setdefault(name, self.zoo.get(name))
                except (KeyError, RuntimeError):
                    pass
        resp.write_json(
            {
                name: e.constrain_stats()
                for name, e in engines.items()
                if getattr(e, "constrain_stats", None) is not None
            }
        )

    def handle_debug_latency(self, req: Request, resp: Response) -> None:
        """Latency waterfall per engine: the per-stage decomposition of
        every finished request's wall (admit_wait / shed / prefill_queue /
        prefill_compute / decode / stall / preempt — an exact partition),
        percentile windows, and the most recent per-request rows.
        `?limit=N` bounds the recent-row tail."""
        try:
            limit = int(req.query.get("limit") or 32)
        except ValueError:
            resp.write_error("limit must be an integer", 400)
            return
        resp.write_json(
            {
                name: {
                    **e.waterfall_stats(),
                    "recent": e.waterfall_recent(limit),
                }
                for name, e in self.gen_engines.items()
                if getattr(e, "waterfall_stats", None) is not None
            }
        )

    def handle_debug_prefix(self, req: Request, resp: Response) -> None:
        """Fleet prefix tier: the knobs, this device's advertised digest,
        route-outcome counters (local / fetch / miss and wire time), and
        each engine's export/import tallies — the one-stop answer to "is
        prefix-locality routing actually hitting?"."""
        from ..routing import prefix as prefix_fp

        resp.write_json(
            {
                "enabled": prefix_fp.prefix_route_enabled(),
                "fetch_min_tokens": prefix_fp.fetch_min_tokens(),
                "transfer_addr": self.transfer_addr,
                "route": self.route_prefix_stats(),
                "digest": self._prefix_digest_tag(),
                "engines": {
                    name: e.prefix_tier_stats()
                    for name, e in self.gen_engines.items()
                    if getattr(e, "prefix_tier_stats", None) is not None
                },
            }
        )

    def handle_debug_profile(self, req: Request, resp: Response) -> None:
        resp.write_json(
            {
                name: e.profile_status()
                for name, e in self.gen_engines.items()
                if getattr(e, "profile_status", None) is not None
            }
        )

    def handle_debug_profile_start(self, req: Request, resp: Response) -> None:
        """Arm a jax.profiler capture for the next N engine-loop steps:
        body {"engine": name?, "steps": N?, "trace_dir": path?}. Defaults to
        the sole generation engine; the engine thread starts/stops the
        capture at loop boundaries (engine._profile_tick)."""
        try:
            body = req.json() or {}
        except Exception:
            resp.write_error("invalid JSON body", 400)
            return
        candidates = {
            name: e
            for name, e in self.gen_engines.items()
            if getattr(e, "start_profile", None) is not None
        }
        if not candidates:
            resp.write_error("no profiling-capable engine", 404)
            return
        name = body.get("engine") or next(iter(candidates))
        eng = candidates.get(name)
        if eng is None:
            resp.write_error(f"unknown engine {name!r}", 404)
            return
        try:
            steps = int(body.get("steps") or 20)
        except (TypeError, ValueError):
            resp.write_error("steps must be an integer", 400)
            return
        status = eng.start_profile(steps, trace_dir=str(body.get("trace_dir") or ""))
        resp.write_json({"engine": name, **status})

    def handle_models(self, req: Request, resp: Response) -> None:
        models = self.catalog.list_models(kind=req.query.get("kind"))
        resp.write_json({"models": models})

    def handle_models_sync(self, req: Request, resp: Response) -> None:
        """Sync cloud models into the catalog (`handlers.go:3176-3287`).
        Without a cloud provider, re-registers local engine models."""
        self.register_local_device()
        synced = len(self.gen_engines) + len(self.embed_engines)
        cloud_synced = 0
        if self.cloud is not None:
            try:
                cloud_synced = sync_cloud_catalog(self.catalog, self.cloud)
            except Exception as e:
                resp.write_json(
                    {"status": "partial", "local": synced, "cloud_error": str(e)}, 502
                )
                return
        resp.write_json({"status": "ok", "local": synced, "cloud": cloud_synced})

    def handle_model_stats(self, req: Request, resp: Response) -> None:
        resp.write_json({"stats": self.catalog.model_stats()})

    def handle_devices(self, req: Request, resp: Response) -> None:
        devices = self.catalog.list_devices()
        for d in devices:
            d["models"] = self.catalog.device_models(d["id"])
            d["circuit"] = self.circuit.status(d["id"])
        resp.write_json({"devices": devices})

    def handle_benchmarks(self, req: Request, resp: Response) -> None:
        resp.write_json({"benchmarks": self.catalog.list_benchmarks()})

    def handle_discovery_run(self, req: Request, resp: Response) -> None:
        t0 = time.time()
        try:
            result = self.discovery.run()
            self.metrics.discovery_runs.labels(status="ok").inc()
            self.metrics.discovery_duration.observe(time.time() - t0)
            self.metrics.devices_online.set(
                len(self.catalog.list_devices(online_only=True))
            )
            resp.write_json({"status": "ok", **result})
        except Exception as e:
            self.metrics.discovery_runs.labels(status="error").inc()
            resp.write_error(f"discovery failed: {e}", 500)

    def handle_costs_summary(self, req: Request, resp: Response) -> None:
        since = req.query.get("since")
        try:
            since_f = float(since) if since else None
        except ValueError:
            resp.write_error("since must be a unix timestamp", 400)
            return
        resp.write_json({"costs": self.catalog.costs_summary(since=since_f)})

    def handle_costs_balance(self, req: Request, resp: Response) -> None:
        if self.cloud is None:
            resp.write_error("no cloud provider configured", 503)
            return
        try:
            bal = self.cloud.balance()
            if bal.get("balance_usd") is not None:
                self.metrics.openrouter_balance.set(bal["balance_usd"])
            resp.write_json(bal)
        except Exception as e:
            resp.write_error(f"balance query failed: {e}", 502)

    def handle_feedback(self, req: Request, resp: Response) -> None:
        body = req.json()
        model = str(body.get("model") or "")
        rating = body.get("rating")
        if not model or rating not in ("up", "down", 1, -1, "+1", "-1"):
            resp.write_error("model and rating (up|down) required", 400)
            return
        self.catalog.record_feedback(model, up=rating in ("up", 1, "+1"))
        resp.write_json({"status": "ok"})

    def handle_knowledge_ingest(self, req: Request, resp: Response) -> None:
        """Proxy to LightRAG / mem0 (`handlers.go:2829-2946`)."""
        body = req.json()
        text = str(body.get("text") or "")
        target = str(body.get("target") or "lightrag")
        import httpx

        if target == "mem0":
            if not self.cfg.mem0_url:
                resp.write_error("MEM0_URL not configured", 503)
                return
            if len(text) < 10:
                resp.write_error("text too short for memory (min 10 chars)", 400)
                return
            try:
                r = httpx.post(
                    f"{self.cfg.mem0_url.rstrip('/')}/v1/memories/",
                    json={"messages": [{"role": "user", "content": text}],
                          "user_id": str(body.get("user_id") or "default")},
                    timeout=30.0,
                )
                resp.write_bytes(r.content, "application/json", r.status_code)
            except Exception as e:
                resp.write_error(f"mem0 unreachable: {e}", 502)
            return
        if not self.cfg.lightrag_url:
            resp.write_error("LIGHTRAG_URL not configured", 503)
            return
        if len(text) < 100:
            resp.write_error("text too short for ingestion (min 100 chars)", 400)
            return
        meta = body.get("metadata") or {}
        if meta:
            header = " | ".join(f"{k}: {v}" for k, v in meta.items())
            text = f"[{header}]\n\n{text}"
        headers = {}
        if self.cfg.lightrag_api_key:
            headers["X-API-Key"] = self.cfg.lightrag_api_key
        try:
            r = httpx.post(
                f"{self.cfg.lightrag_url.rstrip('/')}/documents/text",
                json={"text": text}, headers=headers, timeout=60.0,
            )
            resp.write_bytes(r.content, "application/json", r.status_code)
        except Exception as e:
            resp.write_error(f"lightrag unreachable: {e}", 502)

    def handle_planner_run(self, req: Request, resp: Response) -> None:
        resp.write_json({"status": "ok", "result": self.planner.run_once()})

    def handle_planner_status(self, req: Request, resp: Response) -> None:
        resp.write_json(
            {
                "runs": self.planner.runs,
                "last_run": self.planner.last_run,
                "last_result": self.planner.last_result,
                "interval_s": self.cfg.planner_interval_s,
            }
        )

    # -- lifecycle -----------------------------------------------------------

    def start(self, host: str = "0.0.0.0", port: int = 8080) -> "CoreServer":
        self.api.serve(host, port)
        if not self.advertise_addr:
            self.advertise_addr = f"{host}:{self.api.port}"
        # Peers of this fleet serve on the same port we do: probe it, not
        # the default (slice-metadata hosts, port-less static endpoints,
        # subnet sweeps all derive their target port from this list).
        # TPU_EXTRA_PORTS widens the sweep for fleets with mixed ports
        # (the OLLAMA_PORTS pattern): comma-separated, own port probed first.
        ports = [self.api.port]
        for tok in os.environ.get("TPU_EXTRA_PORTS", "").split(","):
            tok = tok.strip()
            if not tok:
                continue
            try:
                p = int(tok)
            except ValueError:
                log.warning("TPU_EXTRA_PORTS: ignoring non-integer %r", tok)
                continue
            if 0 < p < 65536 and p not in ports:
                ports.append(p)
        self.discovery.ports = ports
        # Cold-start path (doc/performance.md "Cold start & warmup"):
        # critical-prefix AOT compiles run synchronously before the device
        # registers — no request can route here and hit a cold compile —
        # then registration advertises `warming` while the background zoo
        # fills in, then peer warm-fill pulls the fleet's hottest prefix
        # chains so the first shared-prefix request decodes from fetched
        # blocks. TPU_WARMUP=0 / TPU_BOOT_PREFILL_PEERS=0 skip each leg.
        self.boot_warmup()
        # register AFTER the addr is known so peers can proxy to us
        self.register_local_device()
        self._boot_prefix_imported = self.boot_prefix_warm()
        self.limits.apply_specs()
        if self.migration is not None:
            self.migration.start()
        # background tickers: limits re-apply + discovery (main.go:56-67,101-112)
        t = threading.Thread(target=self._ticker, name="core-tickers", daemon=True)
        t.start()
        self._bg_threads.append(t)
        log.info("core server on %s:%d", host, self.api.port)
        return self

    def _ticker(self) -> None:
        last_limits = 0.0
        last_disc = 0.0
        while not self._bg_stop.wait(1.0):
            now = time.time()
            if now - last_limits >= self.cfg.device_limits_interval_s:
                last_limits = now
                try:
                    self.limits.apply_specs()
                except Exception:
                    log.exception("limits re-apply failed")
            if now - last_disc >= self.cfg.discovery_interval_s:
                last_disc = now
                try:
                    self.discovery.run()
                except Exception:
                    log.exception("periodic discovery failed")
            try:
                self.planner.maybe_run(now)
            except Exception:
                log.exception("planner tick failed")
            try:
                self._check_engine_stalls()
            except Exception:
                log.exception("engine stall check failed")

    def _check_engine_stalls(self) -> None:
        """Map a wedged accelerator to device state: while any local engine's
        loop is stalled, the self-device goes OFFLINE (its running jobs'
        leases reset so queue work re-routes — offline_handler.go:12-38
        analog) and the circuit records failures so sync routing fails over
        to other devices/cloud. Recovery flips it back online."""
        if not self.gen_engines or not self.device_id:
            return
        stalled = [n for n, e in self.gen_engines.items() if e.stalled]
        row = self.catalog.get_device(self.device_id)
        online = bool(row and row["online"])
        if stalled and online:
            log.error("local engines stalled (%s): marking %s offline",
                      ", ".join(stalled), self.device_id)
            self.catalog.set_device_online(self.device_id, False)
            self.router.circuit.record(self.device_id, ok=False)
            self.queue.requeue_device_jobs([self.device_id])
            self._stall_offlined = True
        elif not stalled and getattr(self, "_stall_offlined", False):
            # Recovery does NOT flip the device back itself: another path
            # (operator /v1/devices/offline, worker connection-failure
            # reports) may have offlined it during the stall window, and
            # re-onlining here would override that. The periodic discovery
            # tick re-registers the healthy self-device online on its own
            # cadence (register_local_device via Runner.run).
            self._stall_offlined = False

    def shutdown(self) -> None:
        self.tracer.remove_observer(self._observe_span)
        self._bg_stop.set()
        if self.migration is not None:
            self.migration.stop()
        self.api.shutdown()
        for e in self.gen_engines.values():
            e.shutdown()
        if self.zoo is not None:
            self.zoo.shutdown()
        self.db.close()
