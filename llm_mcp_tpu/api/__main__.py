"""`python -m llm_mcp_tpu.api` — boot the core server with local engines.

The process-level analog of the reference's `core/cmd/core/main.go`:
construct state, policy, API; load the configured models into TPU engines;
serve until SIGTERM.
"""

from __future__ import annotations

import logging
import os
import signal
import sys


def main() -> None:
    logging.basicConfig(
        level=os.environ.get("LOG_LEVEL", "INFO"),
        format='{"ts":"%(asctime)s","level":"%(levelname)s","logger":"%(name)s","msg":"%(message)s"}',
    )
    log = logging.getLogger("main")

    from ..utils.config import Config, enable_compile_cache

    cfg = Config()

    from .server import CoreServer

    gen_engines = {}
    embed_engines = {}
    if os.environ.get("TPU_DISABLE_ENGINES", "") not in ("1", "true"):
        # a core with engines compiles for the device: place the cache
        # before the first compile (JAX_COMPILATION_CACHE_DIR, else
        # <checkout>/.jax_cache). JAX_PLATFORMS=cpu is JAX's own switch for
        # CPU sims and needs no help from here.
        enable_compile_cache()
        import jax.numpy as jnp

        from ..executor import EmbeddingEngine, GenerationEngine
        # multi-host first (must precede the first jax op), then the mesh:
        # TPU_MESH_SHAPE="dp=1,tp=8" shards the engines over it; empty = one
        # chip. make_global_mesh lays dp/pp over DCN on multi-slice fleets.
        from ..parallel import distributed

        mesh = None
        if cfg.tpu_mesh_shape:
            multi = distributed.initialize()
            mesh = distributed.make_global_mesh(cfg.tpu_mesh_shape)
            log.info("serving over mesh %s", dict(zip(mesh.axis_names, mesh.devices.shape)))
            if multi and cfg.tpu_slice_cmd_addr:
                # Multi-PROCESS serving: the model spans hosts, so the whole
                # cluster serves as ONE schedulable device — process 0 runs
                # the leader SliceEngine inside CoreServer (registers via
                # discovery exactly like a single-host engine); every other
                # process mirrors dispatches over the command channel and
                # never binds HTTP (executor/engine.py SliceEngine).
                import jax

                from ..executor import SliceEngine

                eng = SliceEngine(
                    cfg.tpu_model,
                    mesh=mesh,
                    cmd_addr=cfg.tpu_slice_cmd_addr,
                    max_slots=cfg.tpu_max_slots,
                    max_seq_len=cfg.tpu_max_seq_len,
                    dtype=jnp.bfloat16,
                    quant=cfg.tpu_quant,
                    weights_dir=cfg.tpu_weights_dir,
                    prefill_chunk=cfg.tpu_prefill_chunk,
                    target_ttft_ms=cfg.tpu_target_ttft_ms,
                )
                if jax.process_index() != 0:
                    log.info("slice follower %d/%d: mirroring dispatches",
                             jax.process_index(), jax.process_count())
                    eng.run_follower()
                    return
                gen_engines[cfg.tpu_model] = eng.start()
        model = cfg.tpu_model
        if model in gen_engines:
            log.info("generation engine: %s (multi-host slice leader)", model)
        else:
            log.info("loading generation engine: %s", model)
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
        emodel = cfg.tpu_embed_model
        cfg.warn_embed_dir_gap(log)
        log.info("loading embedding engine: %s", emodel)
        embed_engines[emodel] = EmbeddingEngine(
            emodel,
            max_seq_len=min(cfg.tpu_max_seq_len, 8192),
            dtype=jnp.bfloat16,
            weights_dir=cfg.tpu_embed_weights_dir,
            quant=cfg.tpu_embed_quant,
        )

    zoo = None
    if gen_engines and cfg.tpu_zoo_models:
        # Model zoo (executor/zoo.py): TPU_ZOO_MODELS co-hosts extra models
        # on this chip. The factory owns every construction kwarg, so a
        # swap-in builds engines identical to the primary one; host_params
        # is None on the cold first load, a parked host tree afterwards.
        from ..executor import ModelZoo

        def _zoo_factory(name, host_params, _mesh=mesh):
            return GenerationEngine(
                name,
                mesh=_mesh,
                params=host_params,
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
            )

        zoo = ModelZoo(_zoo_factory, hot=cfg.tpu_zoo_hot, swap=cfg.tpu_zoo_swap)
        catalog = [
            m.strip() for m in cfg.tpu_zoo_models.split(",")
            if m.strip() and m.strip() not in gen_engines
        ]
        for i, name in enumerate(catalog):
            # the first TPU_ZOO_HOT catalog models load at boot (the hot
            # set); the tail parks until a request pays the swap-in
            zoo.register(name, resident=i < cfg.tpu_zoo_hot)
        log.info(
            "model zoo: %d models (%s resident), hot=%d swap=%s",
            len(catalog), ",".join(zoo.resident_models()) or "none",
            cfg.tpu_zoo_hot, cfg.tpu_zoo_swap,
        )

    host, _, port = cfg.http_addr.rpartition(":")
    server = CoreServer(
        cfg, gen_engines=gen_engines, embed_engines=embed_engines, zoo=zoo
    ).start(host or "0.0.0.0", int(port or 8080))

    grpc_server = None
    if cfg.grpc_addr:
        from ..rpc import GrpcCoreServer

        ghost, _, gport = cfg.grpc_addr.rpartition(":")
        grpc_server = GrpcCoreServer(
            server.queue,
            server.catalog,
            circuit=server.router.circuit,
            device_max_concurrency=cfg.device_max_concurrency,
            default_lease_s=float(cfg.worker_lease_seconds),
        )
        if gen_engines:
            # KV transfer endpoint on the same server: remote migration in,
            # and the fleet prefix tier's PrefixFetch out (handlers must be
            # registered before start). Advertise the dialable address so
            # peers' routers can pull prefixes from this process.
            eng = next(iter(gen_engines.values()))
            grpc_server.enable_kv_transfer(
                eng.migrate_import_stream,
                prefix_export=server.prefix_export,
                prefix_export_hash=server.prefix_export_hash,
            )
            server.transfer_addr = server.transfer_addr or cfg.grpc_addr
        grpc_server.start(f"{ghost or '0.0.0.0'}:{gport or 9090}")
        log.info("grpc worker protocol on %s", cfg.grpc_addr)

    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    signal.signal(signal.SIGINT, lambda *_: stop.append(1))
    try:
        while not stop:
            signal.pause()
    finally:
        log.info("shutting down")
        if grpc_server is not None:
            grpc_server.stop()
        server.shutdown()


if __name__ == "__main__":
    main()
