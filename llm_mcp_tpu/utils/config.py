"""Env-var configuration helpers.

Parity: reference `core/internal/config/config.go:9-34` (Getenv/GetenvInt and
provider key presence checks). The reference uses pure env-var config with no
flag library; we keep that model and add typed helpers plus a `Config` snapshot
object so services can be constructed hermetically in tests.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


def getenv(key: str, default: str = "") -> str:
    v = os.environ.get(key, "")
    return v if v != "" else default


def getenv_int(key: str, default: int) -> int:
    v = os.environ.get(key, "")
    try:
        return int(v)
    except (TypeError, ValueError):
        return default


def getenv_float(key: str, default: float) -> float:
    v = os.environ.get(key, "")
    try:
        return float(v)
    except (TypeError, ValueError):
        return default


def getenv_bool(key: str, default: bool = False) -> bool:
    v = os.environ.get(key, "").strip().lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off"):
        return False
    return default


@dataclass
class Config:
    """Snapshot of all service configuration.

    Mirrors the env catalog of the reference (`compose.yml:26-42`,
    `doc/README.md` env section) with TPU-specific additions.
    """

    # Core service
    http_addr: str = field(default_factory=lambda: getenv("CORE_HTTP_ADDR", ":8080"))
    grpc_addr: str = field(default_factory=lambda: getenv("CORE_GRPC_ADDR", ":9090"))
    db_path: str = field(default_factory=lambda: getenv("DB_PATH", "llmmcp.sqlite3"))

    # Discovery
    discovery_interval_s: int = field(default_factory=lambda: getenv_int("DISCOVERY_INTERVAL", 60))
    tpu_extra_endpoints: str = field(default_factory=lambda: getenv("TPU_EXTRA_ENDPOINTS", ""))
    discovery_scan_subnets: bool = field(default_factory=lambda: getenv_bool("DISCOVERY_SCAN_SUBNETS"))
    discovery_subnets: str = field(default_factory=lambda: getenv("DISCOVERY_SUBNETS", ""))

    # Scheduling / limits
    device_max_concurrency: int = field(default_factory=lambda: getenv_int("DEVICE_MAX_CONCURRENCY", 2))
    strict_model_limits: bool = field(default_factory=lambda: getenv_bool("STRICT_MODEL_LIMITS"))
    device_limits_json: str = field(default_factory=lambda: getenv("DEVICE_LIMITS_JSON", ""))
    device_limits_file: str = field(default_factory=lambda: getenv("DEVICE_LIMITS_FILE", ""))
    device_limits_interval_s: int = field(default_factory=lambda: getenv_int("DEVICE_LIMITS_INTERVAL", 300))
    # planner (background maintenance, see llm_mcp_tpu/planner.py) — the
    # reference documents these knobs for its absent planner/ module
    # (CHANGELOG_V2.md); 0 interval disables the loop entirely.
    planner_interval_s: int = field(default_factory=lambda: getenv_int("PLANNER_INTERVAL", 3600))
    planner_stale_days: float = field(default_factory=lambda: getenv_float("PLANNER_STALE_DAYS", 7.0))
    planner_max_price_per_1m: float = field(default_factory=lambda: getenv_float("PLANNER_MAX_PRICE_PER_1M", 0.0))
    planner_bench_max_age_s: float = field(default_factory=lambda: getenv_float("PLANNER_BENCH_MAX_AGE_S", 0.0))
    benchmark_max_price_per_1m: float = field(default_factory=lambda: getenv_float("BENCHMARK_MAX_PRICE_PER_1M", 10.0))

    # Worker
    worker_id: str = field(default_factory=lambda: getenv("WORKER_ID", ""))
    worker_name: str = field(default_factory=lambda: getenv("WORKER_NAME", ""))
    worker_kinds: str = field(default_factory=lambda: getenv("WORKER_KINDS", ""))
    worker_lease_seconds: int = field(default_factory=lambda: getenv_int("WORKER_LEASE_SECONDS", 30))

    # Cloud providers
    openai_api_key: str = field(default_factory=lambda: getenv("OPENAI_API_KEY", ""))
    openai_base_url: str = field(default_factory=lambda: getenv("OPENAI_BASE_URL", "https://api.openai.com/v1"))
    openrouter_api_key: str = field(default_factory=lambda: getenv("OPENROUTER_API_KEY", ""))
    openrouter_base_url: str = field(
        default_factory=lambda: getenv("OPENROUTER_BASE_URL", "https://openrouter.ai/api/v1")
    )
    cloud_embed_dimensions: int = field(default_factory=lambda: getenv_int("CLOUD_EMBED_DIMENSIONS", 0))

    # Knowledge services
    lightrag_url: str = field(default_factory=lambda: getenv("LIGHTRAG_URL", ""))
    lightrag_api_key: str = field(default_factory=lambda: getenv("LIGHTRAG_API_KEY", ""))
    mem0_url: str = field(default_factory=lambda: getenv("MEM0_URL", ""))

    # Telemetry
    telegram_bot_token: str = field(default_factory=lambda: getenv("TELEGRAM_BOT_TOKEN", ""))
    telegram_chat_id: str = field(default_factory=lambda: getenv("TELEGRAM_CHAT_ID", ""))
    telemetry_interval_s: int = field(default_factory=lambda: getenv_int("TELEMETRY_INTERVAL", 30))
    alert_fail_threshold: int = field(default_factory=lambda: getenv_int("ALERT_FAIL_THRESHOLD", 5))

    # TPU executor
    tpu_model: str = field(default_factory=lambda: getenv("TPU_MODEL", "llama-3.1-8b"))
    tpu_embed_model: str = field(default_factory=lambda: getenv("TPU_EMBED_MODEL", "nomic-embed-text"))
    # "" | int8 — 8B-class embedders (qwen3-embedding-8b) only fit 16 GB int8
    tpu_embed_quant: str = field(default_factory=lambda: getenv("TPU_EMBED_QUANT", ""))
    tpu_weights_dir: str = field(default_factory=lambda: getenv("TPU_WEIGHTS_DIR", ""))
    # the embed model's OWN checkpoint dir — a config.json beside weights is
    # authoritative per engine, so the generator's dir must never leak into
    # the embedder's config resolution (decoder-architecture embedders like
    # qwen3-embedding load real safetensors through this)
    tpu_embed_weights_dir: str = field(
        default_factory=lambda: getenv("TPU_EMBED_WEIGHTS_DIR", "")
    )
    # 32 fits the default llama-3.1-8b KV cache alongside its weights on one chip
    tpu_max_slots: int = field(default_factory=lambda: getenv_int("TPU_MAX_SLOTS", 32))
    tpu_max_seq_len: int = field(default_factory=lambda: getenv_int("TPU_MAX_SEQ_LEN", 2048))
    tpu_mesh_shape: str = field(default_factory=lambda: getenv("TPU_MESH_SHAPE", ""))  # e.g. "dp=1,tp=8"
    # multi-PROCESS serving (executor/engine.py SliceEngine): leader→follower
    # command channel address; non-empty + a jax.distributed triplet puts
    # process 0 in CoreServer as the slice leader, every other process in
    # the follower loop — the whole slice registers as ONE device
    tpu_slice_cmd_addr: str = field(default_factory=lambda: getenv("TPU_SLICE_CMD_ADDR", ""))
    tpu_quant: str = field(default_factory=lambda: getenv("TPU_QUANT", ""))  # "" | int8
    tpu_kv_quant: str = field(default_factory=lambda: getenv("TPU_KV_QUANT", ""))  # "" | int8
    # chunked prefill segment length (tokens); 0 disables interleaved prefill
    tpu_prefill_chunk: int = field(default_factory=lambda: getenv_int("TPU_PREFILL_CHUNK", 512))
    # token-budget scheduler TTFT target (ms): the per-round prefill token
    # budget is clamped so the oldest mid-prefill prompt activates within
    # this deadline (executor/scheduler.py). Replaces the retired
    # TPU_PREFILL_BOOST wall-clock multiplier (doc/performance.md).
    tpu_target_ttft_ms: float = field(default_factory=lambda: getenv_float("TPU_TARGET_TTFT_MS", 2000.0))
    # slot compaction: decode only active rows (auto | on | off)
    tpu_decode_compact: str = field(default_factory=lambda: getenv("TPU_DECODE_COMPACT", "auto"))
    # admission prompt buckets: fine (pow2 + 1.5x midpoints) | pow2
    tpu_prefill_buckets: str = field(default_factory=lambda: getenv("TPU_PREFILL_BUCKETS", "fine"))
    # prompt-prefix KV cache budget in MB (0 disables)
    tpu_prompt_cache_mb: int = field(default_factory=lambda: getenv_int("TPU_PROMPT_CACHE_MB", 256))
    # self-speculative decoding (executor/engine.py draft-and-verify):
    # TPU_SPEC=0 is the kill switch (byte-identical non-speculative decode
    # path); TPU_SPEC_K caps the drafts per verify call; TPU_SPEC_MIN_NGRAM
    # is the shortest suffix the prompt-lookup drafter matches on. The
    # engines read the env directly at construction (TPU_PIPELINE_DEPTH
    # pattern); these fields surface the knobs in config dumps.
    tpu_spec: bool = field(default_factory=lambda: getenv("TPU_SPEC", "1") != "0")
    tpu_spec_k: int = field(default_factory=lambda: getenv_int("TPU_SPEC_K", 7))
    tpu_spec_min_ngram: int = field(default_factory=lambda: getenv_int("TPU_SPEC_MIN_NGRAM", 2))
    # HBM-aware KV pool (executor/memory.py): TPU_KV_HOST_OFFLOAD=1 enables
    # slot preemption with host offload + watermark admission; default off is
    # a true no-op (the pool is never constructed — byte-identical scheduler
    # decisions vs the pool-less engine). TPU_ADMIT_WATERMARK is the offered
    # load multiple of max_slots above which the API sheds (429+Retry-After,
    # deferred job claims); TPU_PREEMPT_POLICY ∈ priority|idle|tokens|
    # slo_debt picks the eviction victim ordering (slo_debt prefers the
    # tenant with the most goodput surplus). Engines read the env directly at
    # construction (TPU_PIPELINE_DEPTH pattern); these fields surface the
    # knobs in config dumps.
    tpu_kv_host_offload: bool = field(default_factory=lambda: getenv_bool("TPU_KV_HOST_OFFLOAD"))
    tpu_admit_watermark: float = field(default_factory=lambda: getenv_float("TPU_ADMIT_WATERMARK", 1.5))
    tpu_preempt_policy: str = field(default_factory=lambda: getenv("TPU_PREEMPT_POLICY", "priority"))
    # extra local API ports for discovery probing (comma-separated; the
    # OLLAMA_PORTS pattern) — multiple executor processes on one host get
    # probed automatically instead of only the pinned self port
    tpu_extra_ports: str = field(default_factory=lambda: getenv("TPU_EXTRA_PORTS", ""))
    # model zoo (executor/zoo.py): TPU_ZOO_MODELS is a comma-separated model
    # catalog co-hosted on this chip ("" = no zoo, byte-identical single-model
    # serving); TPU_ZOO_HOT caps how many stay HBM-resident at once; cold
    # models park as host-RAM param trees and TPU_ZOO_SWAP=0 turns demand
    # swap-in into a hard 503 instead (residency becomes static).
    tpu_zoo_models: str = field(default_factory=lambda: getenv("TPU_ZOO_MODELS", ""))
    tpu_zoo_hot: int = field(default_factory=lambda: getenv_int("TPU_ZOO_HOT", 1))
    tpu_zoo_swap: bool = field(default_factory=lambda: getenv("TPU_ZOO_SWAP", "1") != "0")
    # per-tenant goodput quotas (executor/scheduler.py token buckets):
    # "alice=600,bob=300,*=1000" in tok/s; "" = unmetered (no tenant gate).
    # TPU_TENANT_HEADER renames the request header the tenant id is read
    # from (default X-Tenant-Id, api/inference.py).
    tpu_tenant_quotas: str = field(default_factory=lambda: getenv("TPU_TENANT_QUOTAS", ""))
    tpu_tenant_header: str = field(default_factory=lambda: getenv("TPU_TENANT_HEADER", ""))

    def __post_init__(self) -> None:
        # DB_DSN was documented but never read by any backend (the store is
        # sqlite at DB_PATH, full stop). A silently inert knob is an operator
        # trap — fail loud instead of letting a configured DSN be ignored.
        if os.environ.get("DB_DSN", ""):
            raise RuntimeError(
                "DB_DSN is set but unsupported: the only storage backend is "
                "sqlite at DB_PATH. Unset DB_DSN (or set DB_PATH) to proceed."
            )

    def has_openai(self) -> bool:
        return bool(self.openai_api_key)

    def has_openrouter(self) -> bool:
        return bool(self.openrouter_api_key)

    def warn_embed_dir_gap(self, log) -> None:
        """Deployments that set only TPU_WEIGHTS_DIR: the generator's dir
        deliberately does NOT leak into the embedder (its config.json would
        be authoritative for the wrong model), but the resulting silent
        byte-tokenizer fallback changes embedding outputs — say it out loud
        at every serving entrypoint."""
        if not self.tpu_embed_weights_dir and self.tpu_weights_dir:
            log.warning(
                "TPU_EMBED_WEIGHTS_DIR is unset while TPU_WEIGHTS_DIR=%s: "
                "embedder %s has no checkpoint dir and will use the byte "
                "tokenizer; set TPU_EMBED_WEIGHTS_DIR to its weights dir",
                self.tpu_weights_dir, self.tpu_embed_model,
            )


# enable_compile_cache outcomes, counted not raised: a bad cache dir must
# never take a serving boot down (the engine runs fine, just cold), but the
# failure has to be visible somewhere — chip_smoke.py prints them.
compile_cache_failures = 0
compile_cache_dir: str | None = None

# <checkout>/.jax_cache — where the accelerator entry points keep compiled
# executables when nobody placed the cache from outside. A FIXED path: the
# directory is part of the cache key's world (an entry written under one
# path is only found again under the same one), so never a temp name, pid
# or time.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def compile_cache_path() -> str:
    """The one rule: where `JAX_COMPILATION_CACHE_DIR` is set, that
    directory; where it is not, `<checkout>/.jax_cache`."""
    return getenv("JAX_COMPILATION_CACHE_DIR", "").strip() or DEFAULT_COMPILE_CACHE


def enable_compile_cache(min_compile_s: float = 1.0) -> str | None:
    """Persistent XLA compile cache for every process that compiles for the
    device (`python -m llm_mcp_tpu.api`, the worker with engines,
    chip_smoke.py, benchmark/run.py, tests/conftest.py): first 8B compiles cost
    tens of seconds to minutes each, and a restart would otherwise re-pay
    the whole executable zoo (prompt buckets, compact buckets, admit
    shapes). The warmup planner's background AOT compiles land here too,
    which is what makes them stick for the next boot (warmup_pack.py).

    Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and this
    function gives `jax.config` no other directory; where it is not, the
    fixed `DEFAULT_COMPILE_CACHE` is configured. A cache written on one
    machine is for that machine: CPU entries can carry target features
    another host lacks, and a compile for a *described* chip cannot be
    loaded without one (tests/test_tpu_compile.py turns the cache off
    around its compiles).

    Failures COUNT (module counter `compile_cache_failures`), never raise:
    an unwritable cache dir degrades to a cold boot, not a dead one.
    Returns the active cache dir, or None when it could not be used."""
    import logging as _logging

    global compile_cache_failures, compile_cache_dir
    from_env = bool(getenv("JAX_COMPILATION_CACHE_DIR", "").strip())
    cache_dir = compile_cache_path()
    import jax

    try:
        os.makedirs(cache_dir, exist_ok=True)
        if not os.access(cache_dir, os.W_OK | os.X_OK):
            raise PermissionError(f"{cache_dir} is not writable")
        if not from_env:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(min_compile_s)
        )
    except Exception:  # noqa: BLE001 — counted, not raised (see docstring)
        compile_cache_failures += 1
        _logging.getLogger("config").warning(
            "compile cache at %s unavailable (failure #%d)",
            cache_dir, compile_cache_failures, exc_info=True,
        )
        return None
    compile_cache_dir = cache_dir
    return cache_dir
