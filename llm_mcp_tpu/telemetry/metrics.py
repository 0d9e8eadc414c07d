"""Prometheus metrics registry.

Parity: reference `core/internal/metrics/metrics.go:10-115` — same 11
collector names/labels so existing dashboards keep working, plus TPU-native
additions (engine slot occupancy, decode throughput, TTFT).

The reference's `llmcore_jobs_created_total` was declared but never
incremented (dead metric, SURVEY §5); here it is wired up at submit.
"""

from __future__ import annotations

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
    CONTENT_TYPE_LATEST,
)


class Metrics:
    def __init__(self, registry: CollectorRegistry | None = None):
        self.registry = registry or CollectorRegistry()
        r = self.registry

        # -- reference-parity collectors (metrics.go:10-115) --
        self.embedding_requests = Counter(
            "llmcore_embedding_requests_total",
            "Embedding requests",
            ["model", "device", "status"],
            registry=r,
        )
        self.embedding_duration = Histogram(
            "llmcore_embedding_duration_seconds",
            "Embedding request duration",
            ["model"],
            buckets=(0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60),
            registry=r,
        )
        self.embedding_input_tokens = Counter(
            "llmcore_embedding_input_tokens_total",
            "Embedding input tokens",
            ["model"],
            registry=r,
        )
        self.jobs_created = Counter(
            "llmcore_jobs_created_total", "Jobs created", ["kind"], registry=r
        )
        self.devices_online = Gauge(
            "llmcore_devices_online", "Devices online", registry=r
        )
        self.discovery_runs = Counter(
            "llmcore_discovery_runs_total", "Discovery runs", ["status"], registry=r
        )
        self.discovery_duration = Histogram(
            "llmcore_discovery_duration_seconds",
            "Discovery run duration",
            registry=r,
        )
        self.chat_requests = Counter(
            "llmcore_chat_requests_total",
            "Chat requests",
            ["model", "provider", "status"],
            registry=r,
        )
        self.chat_duration = Histogram(
            "llmcore_chat_duration_seconds",
            "Chat request duration",
            ["model", "provider"],
            buckets=(0.5, 1, 2.5, 5, 10, 30, 60, 120),
            registry=r,
        )
        self.chat_tokens = Counter(
            "llmcore_chat_tokens_total",
            "Chat tokens",
            ["model", "provider", "direction"],
            registry=r,
        )
        self.chat_cost_usd = Counter(
            "llmcore_chat_cost_usd_total",
            "Chat cost USD",
            ["model", "provider"],
            registry=r,
        )
        self.openrouter_balance = Gauge(
            "llmcore_openrouter_balance_usd", "OpenRouter balance", registry=r
        )

        # -- TPU-native additions --
        self.engine_slots_in_use = Gauge(
            "llmtpu_engine_slots_in_use", "Generation engine slots occupied", registry=r
        )
        self.engine_tps = Gauge(
            "llmtpu_engine_decode_tok_per_s",
            "Decode tokens/sec over the last 10s window",
            registry=r,
        )
        self.chat_ttft = Histogram(
            "llmtpu_chat_ttft_seconds",
            "Time to first token",
            ["model"],
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10),
            registry=r,
        )
        # Fed from trace spans (tracing.py observer wired in CoreServer):
        # stage ∈ {queue_wait, route, rpc, prefill, decode}.
        self.stage_duration = Histogram(
            "llmtpu_stage_duration_seconds",
            "Per-request stage latency, derived from trace spans",
            ["stage"],
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60),
            registry=r,
        )
        # Token-budget scheduler (executor/scheduler.py): the live per-round
        # prefill token budget, how full decode dispatches run, and how often
        # the TTFT deadline demanded more prefill than the fairness cap
        # allows (starvation — raise TPU_TARGET_TTFT_MS, add capacity, or
        # shed load; doc/performance.md).
        self.sched_prefill_token_budget = Gauge(
            "llmtpu_sched_prefill_token_budget",
            "Prefill token budget of the engine's most recent scheduling decision",
            registry=r,
        )
        self.sched_decode_occupancy = Gauge(
            "llmtpu_sched_decode_batch_occupancy",
            "Active decode rows / max_slots in the most recent dispatch",
            registry=r,
        )
        self.sched_starved_rounds = Counter(
            "llmtpu_sched_starved_rounds_total",
            "Rounds where the TTFT deadline needed more prefill tokens than the fairness cap",
            registry=r,
        )
        # Self-speculative decoding (executor/engine.py draft-and-verify,
        # TPU_SPEC knobs; doc/performance.md). Per-engine labels match the
        # scheduler gauges' wiring in api/server.py engines_info.
        self.spec_accept_rate = Gauge(
            "llmtpu_spec_accept_rate",
            "Accepted / drafted speculative tokens (cumulative ratio)",
            ["engine"],
            registry=r,
        )
        self.spec_tok_per_call = Gauge(
            "llmtpu_spec_tok_per_verify_call",
            "Tokens emitted per speculative verify dispatch (cumulative ratio)",
            ["engine"],
            registry=r,
        )
        self.spec_drafted_tokens = Counter(
            "llmtpu_spec_drafted_tokens_total",
            "Draft tokens proposed by the n-gram drafter",
            ["engine"],
            registry=r,
        )
        self.spec_emitted_tokens = Counter(
            "llmtpu_spec_emitted_tokens_total",
            "Tokens emitted by speculative verify rounds (accepted + final samples)",
            ["engine"],
            registry=r,
        )
        # HBM-aware KV pool (executor/memory.py, TPU_KV_HOST_OFFLOAD):
        # headroom is the fraction of shed-free capacity left (0 = the API
        # is shedding); the counters are advanced by delta from the engines'
        # cumulative totals in api/server.py engines_info, like the
        # scheduler/speculation bridges above.
        self.kv_pool_headroom = Gauge(
            "llmtpu_kv_pool_headroom",
            "Fraction of admission capacity remaining before load shedding",
            ["engine"],
            registry=r,
        )
        self.kv_preempted = Counter(
            "llmtpu_kv_preempt_total",
            "Slots preempted and offloaded to host memory",
            ["engine"],
            registry=r,
        )
        self.kv_restored = Counter(
            "llmtpu_kv_restore_total",
            "Preempted slots restored from host memory",
            ["engine"],
            registry=r,
        )
        self.kv_shed = Counter(
            "llmtpu_kv_shed_total",
            "Requests shed above the admission watermark (429 or deferred claim)",
            ["engine"],
            registry=r,
        )
        # Paged KV block economy (executor/paging.py, TPU_KV_BLOCK_TOKENS):
        # gauges read straight from paging_stats(); the COW counter is
        # bridged by delta like the pool counters above. sharing_ratio is
        # logical/physical blocks — >1 means prefix sharing is multiplying
        # capacity; leaks must stay 0 (tests/test_paging.py asserts it).
        self.kv_blocks_used = Gauge(
            "llmtpu_kv_blocks_used",
            "Physical KV blocks with a live refcount",
            ["engine"],
            registry=r,
        )
        self.kv_block_sharing = Gauge(
            "llmtpu_kv_block_sharing_ratio",
            "Logical / physical KV blocks (prefix-sharing multiplier)",
            ["engine"],
            registry=r,
        )
        self.kv_cow_copies = Counter(
            "llmtpu_kv_cow_copies_total",
            "Boundary blocks copied-on-write at shared-prefix admission",
            ["engine"],
            registry=r,
        )
        self.kv_block_leaks = Gauge(
            "llmtpu_kv_block_leaks",
            "Blocks the paging ledger audit flags as leaked/double-freed (must be 0)",
            ["engine"],
            registry=r,
        )

        # -- KV migration (executor/migration.py) --
        self.kv_migrated_out = Counter(
            "llmtpu_kv_migrate_out_total",
            "Snapshots exported to another engine (drain or prefill handoff)",
            ["engine"],
            registry=r,
        )
        self.kv_migrated_in = Counter(
            "llmtpu_kv_migrate_in_total",
            "Snapshots imported and restored from another engine",
            ["engine"],
            registry=r,
        )
        self.kv_migrate_bytes = Counter(
            "llmtpu_kv_migrate_bytes_total",
            "Wire bytes of exported KV migration payloads",
            ["engine"],
            registry=r,
        )
        self.kv_migrate_requeues = Counter(
            "llmtpu_kv_migrate_requeue_total",
            "Queued requests re-homed to an idle engine without KV transfer",
            registry=r,
        )
        self.kv_migration_headroom_delta = Gauge(
            "llmtpu_kv_migration_headroom_delta",
            "Max-min kv_headroom spread across local engines (drain trigger signal)",
            registry=r,
        )

        # -- Prefix-locality routing / fleet prefix tier (routing/prefix.py,
        # TPU_PREFIX_ROUTE / TPU_PREFIX_FETCH_MIN_TOKENS; doc/performance.md).
        # outcome: local = the serving engine already held the longest known
        # prefix; fetch = a peer's chain was pulled over PrefixFetch and
        # admitted pin-only; miss = nobody held a usable prefix.
        self.route_prefix_hit = Counter(
            "llmtpu_route_prefix_hit_total",
            "Prefix-locality routing decisions by outcome",
            ["outcome"],
            registry=r,
        )
        self.route_prefix_matched_tokens = Histogram(
            "llmtpu_route_prefix_matched_tokens",
            "Prompt tokens covered by a resident (or fetched) prefix chain at route time",
            buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096, 8192),
            registry=r,
        )
        # Engine-side tier counters, advanced by delta from prefix_tier_stats()
        # in api/server.py engines_info like the pool/paging bridges above.
        self.prefix_tier_exports = Counter(
            "llmtpu_prefix_tier_exports_total",
            "Prefix chains exported to peers over the PrefixFetch RPC",
            ["engine"],
            registry=r,
        )
        self.prefix_tier_imports = Counter(
            "llmtpu_prefix_tier_imports_total",
            "Peer prefix chains imported and pinned into the local cache",
            ["engine"],
            registry=r,
        )
        self.prefix_tier_bytes = Counter(
            "llmtpu_prefix_tier_bytes_total",
            "Wire bytes of prefix-tier payloads by direction",
            ["engine", "direction"],
            registry=r,
        )
        self.prefix_tier_rejects = Counter(
            "llmtpu_prefix_tier_import_rejects_total",
            "Peer prefix payloads rejected (geometry mismatch, no budget, bad header)",
            ["engine"],
            registry=r,
        )

        # -- Flight recorder / anomaly dumps / compile ledger --
        # (telemetry/recorder.py, TPU_FLIGHT knobs; doc/observability.md).
        # The recorder itself is stdlib-only, so all Prometheus bridging
        # happens here + in api/server.py engines_info, by delta like the
        # pool/paging/migration counters above.
        self.flight_events = Counter(
            "llmtpu_flight_events_total",
            "Step events accepted into the flight-recorder ring (process-wide)",
            registry=r,
        )
        self.flight_dropped = Gauge(
            "llmtpu_flight_dropped_events",
            "Events dropped while the ring was frozen mid-dump (must be 0)",
            registry=r,
        )
        self.anomaly_dumps = Counter(
            "llmtpu_anomaly_dumps_total",
            "Anomaly-triggered flight-ring journal dumps",
            ["engine", "detector"],
            registry=r,
        )
        self.watchdog_transitions = Counter(
            "llmtpu_watchdog_transitions_total",
            "Engine watchdog state transitions "
            "(compile_grace / stalled / shed / shed_in_grace / recovered)",
            ["engine", "state"],
            registry=r,
        )
        # Fed from CompileLedger.drain_fresh() at engines_info refresh:
        # one observation per first dispatch of an executable shape. hit is
        # JAX's own answer for that dispatch (hit / miss; unknown where JAX
        # made no compile request to the persistent cache).
        self.compile_seconds = Histogram(
            "llmtpu_compile_seconds",
            "Wall time of serve-path executable compiles, per phase and cache outcome",
            ["engine", "phase", "hit"],
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 20, 40, 80, 160),
            registry=r,
        )

        # -- Perf observatory (telemetry/perf.py, TPU_PERF_SAMPLE /
        # TPU_TARGET_ITL_MS; doc/observability.md). ITL samples are drained
        # from each engine's observatory at engines_info refresh (exactly
        # once, like compile_seconds); goodput/roofline gauges read straight
        # from perf_stats(); the sampled phase-walls counters advance by
        # delta like the pool/paging bridges above.
        self.itl_seconds = Histogram(
            "llmtpu_itl_seconds",
            "Inter-token latency (TPOT): per-token share of each emission round's wall gap",
            ["engine"],
            buckets=(0.002, 0.005, 0.01, 0.02, 0.04, 0.08, 0.15, 0.3, 0.6, 1.2, 2.5, 5),
            registry=r,
        )
        self.goodput_tok_per_s = Gauge(
            "llmtpu_goodput_tok_per_s",
            "Tokens/s from requests meeting the joint TTFT+ITL SLO (60s window)",
            ["engine"],
            registry=r,
        )
        self.goodput_ratio = Gauge(
            "llmtpu_goodput_ratio",
            "SLO-conforming / total finished tokens (cumulative)",
            ["engine"],
            registry=r,
        )
        # per-tenant split of the same ledgers (model zoo tenancy): series
        # only materialize for tenants that actually sent traffic, so the
        # single-tenant scrape surface is unchanged
        self.goodput_tok_per_s_tenant = Gauge(
            "llmtpu_goodput_tok_per_s_tenant",
            "Per-tenant tokens/s from requests meeting the joint SLO (60s window)",
            ["engine", "tenant"],
            registry=r,
        )
        self.goodput_ratio_tenant = Gauge(
            "llmtpu_goodput_ratio_tenant",
            "Per-tenant SLO-conforming / finished tokens (cumulative)",
            ["engine", "tenant"],
            registry=r,
        )
        self.tenant_shed_total = Counter(
            "llmtpu_tenant_shed_total",
            "Admission 429s charged to a tenant (quota or capacity shed)",
            ["engine", "tenant"],
            registry=r,
        )
        self.decode_mfu = Gauge(
            "llmtpu_decode_mfu",
            "Model FLOPs utilization of sampled decode rounds vs the chip's published peak (perf.CHIP_PEAKS)",
            ["engine"],
            registry=r,
        )
        self.decode_mbu = Gauge(
            "llmtpu_decode_mbu",
            "HBM bandwidth utilization of sampled decode rounds vs the chip's published peak (perf.CHIP_PEAKS)",
            ["engine"],
            registry=r,
        )
        self.perf_phase_seconds = Counter(
            "llmtpu_perf_phase_seconds_total",
            "Sampled engine-loop wall seconds by dispatch phase and bucket "
            "(host staging / device compute / scheduler wait)",
            ["engine", "phase", "bucket"],
            registry=r,
        )
        # latency waterfall (telemetry/workload.py): each finished request's
        # wall decomposed into an exact stage partition; cumulative per-stage
        # seconds advance by delta in the engines_info bridge (server.py)
        self.latency_stage_seconds = Counter(
            "llmtpu_latency_stage_seconds",
            "Finished-request wall seconds by waterfall stage (admit_wait / "
            "shed / prefill_queue / prefill_compute / decode / stall / preempt)",
            ["engine", "stage"],
            registry=r,
        )

    def render(self) -> tuple[bytes, str]:
        return generate_latest(self.registry), CONTENT_TYPE_LATEST
