"""`python -m llm_mcp_tpu.worker` — boot a pull worker.

Env-configured like the reference worker container (compose.yml llmworker
service): CORE_URL points at the core; TPU engines load in-process when
WORKER_LOAD_ENGINES=1 (the TPU-VM deployment shape), otherwise jobs proxy
to routed device addrs.
"""

from __future__ import annotations

import logging
import os
import signal


def main() -> None:
    logging.basicConfig(
        level=os.environ.get("LOG_LEVEL", "INFO"),
        format='{"ts":"%(asctime)s","level":"%(levelname)s","logger":"%(name)s","msg":"%(message)s"}',
    )
    from ..api.providers import CloudClient
    from ..utils.config import Config, enable_compile_cache
    from .client import CoreClient
    from .executors import Executors
    from .worker import Worker

    cfg = Config()
    core_url = os.environ.get("CORE_URL", "http://localhost:8080")

    gen_engines: dict = {}
    embed_engines: dict = {}
    if os.environ.get("WORKER_LOAD_ENGINES", "") in ("1", "true"):
        # only a worker with engines compiles anything; a proxy-only worker
        # never imports jax
        enable_compile_cache()
        import jax.numpy as jnp

        from ..executor import EmbeddingEngine, GenerationEngine
        from ..parallel import distributed

        mesh = None
        if cfg.tpu_mesh_shape:
            distributed.initialize()
            mesh = distributed.make_global_mesh(cfg.tpu_mesh_shape)

        model = cfg.tpu_model
        gen_engines[model] = GenerationEngine(
            model,
            mesh=mesh,
            max_slots=cfg.tpu_max_slots,
            max_seq_len=cfg.tpu_max_seq_len,
            dtype=jnp.bfloat16,
            weights_dir=cfg.tpu_weights_dir,
            quant=cfg.tpu_quant,
            kv_quant=cfg.tpu_kv_quant,
            prefill_chunk=cfg.tpu_prefill_chunk,
            decode_compact=cfg.tpu_decode_compact,
            prompt_cache_mb=cfg.tpu_prompt_cache_mb,
            prefill_buckets=cfg.tpu_prefill_buckets,
            target_ttft_ms=cfg.tpu_target_ttft_ms,
        ).start()
        cfg.warn_embed_dir_gap(logging.getLogger("worker"))
        embed_engines[cfg.tpu_embed_model] = EmbeddingEngine(
            cfg.tpu_embed_model,
            max_seq_len=min(cfg.tpu_max_seq_len, 8192),
            dtype=jnp.bfloat16,
            weights_dir=cfg.tpu_embed_weights_dir,
            quant=cfg.tpu_embed_quant,
        )

    cloud = CloudClient(cfg) if (cfg.has_openrouter() or cfg.has_openai()) else None
    # gRPC transport when configured (reference worker parity: gRPC-only,
    # `main.py:536-599`); HTTP otherwise. Worker is transport-agnostic.
    grpc_target = os.environ.get("CORE_GRPC_TARGET", "")
    client = CoreClient(core_url)
    if grpc_target:
        try:
            from ..rpc.client import GrpcCoreClient

            client = GrpcCoreClient(grpc_target)
        except Exception as e:
            # Downgrading to HTTP is only safe when CORE_URL was explicitly
            # configured — otherwise fail fast instead of silently spinning
            # against the localhost default.
            if not os.environ.get("CORE_URL"):
                raise SystemExit(
                    f"CORE_GRPC_TARGET={grpc_target!r} set but gRPC client "
                    f"unavailable ({e}) and no CORE_URL fallback configured"
                ) from e
            logging.getLogger("main").warning(
                "gRPC unavailable (%s); falling back to HTTP at %s", e, core_url
            )
    worker = Worker(
        client,
        Executors(gen_engines=gen_engines, embed_engines=embed_engines, cloud=cloud),
        worker_id=cfg.worker_id,
        name=cfg.worker_name,
        kinds=[k.strip() for k in cfg.worker_kinds.split(",") if k.strip()],
        lease_seconds=float(cfg.worker_lease_seconds),
    )
    signal.signal(signal.SIGTERM, lambda *_: worker.stop())
    signal.signal(signal.SIGINT, lambda *_: worker.stop())
    worker.run()
    for e in gen_engines.values():
        e.shutdown()


if __name__ == "__main__":
    main()
