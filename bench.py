"""Round benchmark: steady-state decode throughput of the serving stack on
the available accelerator (one real TPU chip under the driver; CPU when
forced).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Baseline of record (BASELINE.md row 3): 2000 tok/s/chip, Llama-3.1-8B
streaming chat on v5e. The headline metric IS the 8B config: weight-only
int8 (~8.0 GB) + int8 KV cache fits a single 16 GB v5e chip at B=112
slots, so the fight happens on the baseline's own model, not a stand-in.
Secondary metrics (same JSON object, "secondary" key) cover the 1B config.

Env knobs for sweeps (defaults are the driver configuration):
  BENCH_MODEL / BENCH_B / BENCH_S / BENCH_K  — raw-loop shape override
  BENCH_SECONDARY=0                          — headline only
  BENCH_PREFIX_ROUTE=0                       — skip the 2-engine
                                               prefix-locality routing sweep
  BENCH_POISSON_RPS=<rate>                   — open-loop Poisson-burst
                                               arrivals for the routing
                                               sweep's clients (aggregate
                                               requests/s; 0 = closed loop)
  BENCH_TRACE=<path | synth:kind:n[:seed]>   — dedicated trace-replay mode:
                                               re-issue a captured (or
                                               synthesized chat/embed/
                                               longctx/agent) workload
                                               open-loop with faithful
                                               inter-arrival gaps, then
                                               print the replay line of
                                               record and exit
  BENCH_TRACE_COMPRESS=<x>                   — time-compression factor for
                                               replay gaps (default 1 =
                                               real time)
  BENCH_TRACE_SEED=<n>                       — replay stream seed (two runs
                                               with the same seed issue
                                               byte-identical streams)
  BENCH_REPLAY=0                             — skip the CPU capture→replay
                                               smoke leg
  BENCH_DISPATCH=0                           — skip the pp×tp unified-
                                               dispatch parity sweep
  BENCH_DISPATCH_MESH=<spec>                 — mesh for that sweep
                                               (default pp=2,tp=2)
"""

from __future__ import annotations

import gc
import json
import os
import time

from llm_mcp_tpu.utils.platform import device_platform


def bench_poisson_rps() -> float:
    """BENCH_POISSON_RPS parsed in ONE place (it used to be read
    independently at each sweep call site): the aggregate open-loop
    arrival rate in requests/s; 0 keeps clients closed-loop."""
    try:
        return float(os.environ.get("BENCH_POISSON_RPS", "0") or 0.0)
    except ValueError:
        return 0.0


def next_arrival_gap(
    rng,
    *,
    poisson_rps: float = 0.0,
    n_clients: int = 1,
    trace_gap: float | None = None,
    compress: float = 1.0,
) -> float:
    """The one arrival process every open-loop mode draws from: a captured
    trace's inter-arrival gap scaled by the time-compression factor when
    given, else a Poisson gap at the aggregate rate split across the
    clients, else 0 (closed loop). `rng` is each caller's own seeded
    random.Random — the draw sequence stays per-client deterministic."""
    if trace_gap is not None:
        return max(0.0, float(trace_gap)) / max(1e-9, compress)
    if poisson_rps > 0:
        return rng.expovariate(poisson_rps / max(1, n_clients))
    return 0.0


def raw_decode_tps(
    model: str,
    B: int,
    S: int,
    K: int,
    rounds: int,
    kv_int8: bool = False,
    stats: dict | None = None,
) -> float:
    """Steady-state tok/s of the jitted decode loop (chunked scan with
    fused sampling — the same decode program GenerationEngine dispatches
    per chunk, minus the engine's host-side admission/emission work, which
    the serving-path metric measures separately). When `stats` is passed,
    "weight_bytes" is filled in so the caller can derive the layer pass's
    achieved weight-stream bandwidth (layers_gbps = bytes x steps/s)."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_mcp_tpu.kernels.attention import resolve_decode_impl
    from llm_mcp_tpu.models import get_config, init_kv_cache, llama_decode_step
    from llm_mcp_tpu.models.quant import (
        fuse_layer_weights,
        init_llama_params_quantized,
        quantized_bytes,
    )
    from llm_mcp_tpu.ops.sampling import sample_tokens

    cfg = get_config(model)
    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    # direct int8 init: 8B bf16 (16 GB) cannot be materialized-then-quantized
    # on one v5e chip, so the quantized tree is built in place
    params = init_llama_params_quantized(cfg, jax.random.PRNGKey(0), scale_dtype=dtype)
    if os.environ.get("LLM_MCP_TPU_FUSE_QKV", "1") != "0":
        # the single-chip wqkv/w13 fusion GenerationEngine applies — the raw
        # loop must measure the production layer pass, not the unfused one
        params = fuse_layer_weights(params)
    if stats is not None:
        stats["weight_bytes"] = float(quantized_bytes(params)[0])
    cache = init_kv_cache(cfg, B, S, dtype=dtype, quantized=kv_int8)
    impl = resolve_decode_impl(quantized=kv_int8)

    @partial(jax.jit, donate_argnums=(1, 2))
    def decode_chunk(params, ck, cv, tokens, lengths, rng):
        def step(carry, _):
            ck, cv, toks, lens, rng = carry
            logits, ck, cv = llama_decode_step(
                cfg, params, ck, cv, toks, lens, attn_impl=impl
            )
            rng, sub = jax.random.split(rng)
            new = sample_tokens(
                logits,
                sub,
                jnp.full((toks.shape[0],), 0.7, dtype=jnp.float32),
                jnp.zeros((toks.shape[0],), dtype=jnp.int32),
                jnp.ones((toks.shape[0],), dtype=jnp.float32),
            )
            return (ck, cv, new, lens + 1, rng), new

        (ck, cv, toks, lens, rng), out = jax.lax.scan(
            step, (ck, cv, tokens, lengths, rng), None, length=K
        )
        return out, ck, cv, toks, lens

    ck, cv = cache["k"], cache["v"]
    toks = jnp.zeros((B,), dtype=jnp.int32)
    lens = jnp.zeros((B,), dtype=jnp.int32)
    rng = jax.random.PRNGKey(1)

    # warmup / compile. Sync via a device->host FETCH of the final output.
    # A fetch of the final output is data-dependent on every chained step,
    # so it bounds the full computation.
    out, ck, cv, toks, lens = decode_chunk(params, ck, cv, toks, lens, rng)
    np.asarray(out)

    t0 = time.perf_counter()
    for _ in range(rounds):
        out, ck, cv, toks, lens = decode_chunk(params, ck, cv, toks, lens, rng)
    np.asarray(out)
    dt = time.perf_counter() - t0
    return rounds * K * B / dt


class _SkipDirect(Exception):
    pass


def recorder_append_cost_s(n: int = 100_000) -> float:
    """Measured wall per FlightRecorder.event() append — a tight loop on a
    private ring running the exact code path the serve-path singleton runs.
    Multiplied by a window's event count it prices the recorder's share of
    serve wall (the <1% acceptance bar). 0.0 when TPU_FLIGHT=0 disables
    the ring (the no-op path costs one env read per call)."""
    import tempfile

    from llm_mcp_tpu.telemetry.recorder import FlightRecorder

    with tempfile.TemporaryDirectory(prefix="llmtpu-flight-bench-") as td:
        rec = FlightRecorder(capacity=4096, dump_dir=td)
        if not rec.enabled:
            return 0.0
        t0 = time.perf_counter()
        for i in range(n):
            rec.event("decode", rows=8, i=i)
        return (time.perf_counter() - t0) / n


def serve_efficiency(serve: dict) -> float | None:
    """serve tok/s ÷ engine-direct tok/s, the serving-layer tax as ONE
    first-class tracked number (scripts/perf_gate.py gates on it): 1.0
    means the serve path delivers the engine's full decode rate; r05's
    regression was 0.295 hiding in plain sight across two fields. None
    when the direct measurement was unavailable."""
    direct = serve.get("engine_direct_tok_per_s", 0.0)
    if direct and direct > 0:
        return serve.get("tok_per_s", 0.0) / direct
    return None


def serve_path_metrics(
    model: str,
    *,
    n_clients: int,
    max_tokens: int,
    measure_s: float,
    quant: str = "int8",
    kv_quant: str = "int8",
    max_slots: int = 64,
    max_seq_len: int = 1024,
    decode_chunk: int = 16,
    admit_batch: int = 4,
    warmup_timeout_s: float = 900.0,
    decode_compact: str = "auto",
    measure_direct: bool = True,
    workload: str = "unique",
) -> dict[str, float]:
    """Steady-state tok/s and client-observed p50 TTFT through the REAL
    serving path — GenerationEngine behind CoreServer's /v1/chat/completions
    SSE (the metric of record, BASELINE.md line 28), not the raw decode loop.

    Token counts come from the engine's host-side total_tokens counter
    sampled at the measurement window edges (exact); TTFT is wall time from
    request POST to the first SSE content delta, over requests *started*
    inside the window (so compile warmup never pollutes it).
    """
    import statistics
    import subprocess
    import sys
    import threading

    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.api.server import CoreServer
    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.state.db import Database
    from llm_mcp_tpu.utils.config import Config

    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    eng = GenerationEngine(
        model,
        max_slots=max_slots,
        max_seq_len=max_seq_len,
        dtype=dtype,
        decode_chunk=decode_chunk,
        quant=quant,
        kv_quant=kv_quant,
        admit_batch=admit_batch,
        decode_compact=decode_compact,
    ).start()
    srv = CoreServer(
        Config(), db=Database(":memory:"), gen_engines={model: eng}, embed_engines={}
    ).start("127.0.0.1", 0)
    url = f"http://127.0.0.1:{srv.api.port}/v1/chat/completions"
    # Realistic chat traffic: a SHARED ~170-token system preamble + a unique
    # per-client question (client_proc appends it). Total ~200 byte-tokens
    # fits the 256 prompt bucket. The shared prefix exercises the engine's
    # prompt-prefix KV cache exactly the way production system prompts do —
    # while the unique suffixes keep every request's prefill honest.
    prompt = (
        "you are a precise assistant serving a latency benchmark suite. "
        "answer each question directly, with no preamble and no filler. "
        "keep every answer to a single short line of plain text. "
    )  # ~170 bytes; + ~60-byte client suffix stays inside the 256 bucket

    # Clients run in SEPARATE PROCESSES (the --client-proc mode below, pure
    # stdlib, no jax import): real clients are remote, and 80 in-process
    # SSE-parsing threads contend the server's GIL hard enough to become
    # the bottleneck being measured (~20% at 8B B=80). 4 procs x B/4
    # threads keeps any one client process from saturating its own GIL.
    nprocs = min(4, n_clients)
    sizes = [n_clients // nprocs + (1 if i < n_clients % nprocs else 0)
             for i in range(nprocs)]
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--client-proc",
             url, str(sz), str(max_tokens), model, prompt, workload],
            stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
        )
        for sz in sizes
    ]
    lock = threading.Lock()
    ttft_records: list[tuple[float, float]] = []  # (t_post, t_first) epoch s
    shed_records: list[tuple[float, float]] = []  # (t_shed, retry_after_s)
    warmed: list[int] = []  # procs whose every client has a round-trip done

    def reader(p: subprocess.Popen) -> None:
        for line in p.stdout:
            try:
                if line.startswith("TTFT "):
                    parts = line.split()
                    with lock:
                        ttft_records.append((float(parts[1]), float(parts[2])))
                elif line.startswith("SHED "):
                    parts = line.split()
                    with lock:
                        shed_records.append((float(parts[1]), float(parts[2])))
                elif line.startswith("WARMED"):
                    with lock:
                        warmed.append(1)
                elif line.startswith("#"):
                    print(line.rstrip(), flush=True)
            except (ValueError, IndexError):
                # concurrent client threads can interleave stdout lines;
                # a mangled record is dropped, never fatal to the reader
                pass

    readers = [threading.Thread(target=reader, args=(p,), daemon=True) for p in procs]
    t_start = time.perf_counter()
    for t in readers:
        t.start()
    # Warmup: every client in every process has a full round-trip behind it
    # (all executables compiled, slots saturated) — a few fast clients
    # looping must not open the window early.
    while time.perf_counter() - t_start < warmup_timeout_s:
        with lock:
            if len(warmed) >= nprocs:
                break
        time.sleep(0.25)
    # ...and the executable-shape set has stopped growing: staggered client
    # arrivals hit pow2 admit/compact/chunk buckets one at a time, and a
    # first compile landing INSIDE the measured window tanks it (profiled on
    # the CPU harness: the round-3 serve-vs-engine gap was mostly compile
    # churn, not SSE delivery — per-token delivery CPU is negligible warm).
    shape_deadline = time.perf_counter() + min(120.0, warmup_timeout_s)
    stable_since = time.perf_counter()
    n_shapes = len(getattr(eng, "_seen_exec_shapes", ()))
    while time.perf_counter() < shape_deadline:
        cur = len(getattr(eng, "_seen_exec_shapes", ()))
        if cur != n_shapes:
            n_shapes, stable_since = cur, time.perf_counter()
        elif time.perf_counter() - stable_since >= 5.0:
            break
        time.sleep(0.5)

    from llm_mcp_tpu.telemetry.recorder import get_recorder

    rec = get_recorder()
    with eng.stats_lock:
        tok0, err0 = eng.total_tokens, eng.total_errors
        fin0, ftok0 = eng.finished_requests, eng.finished_tokens
    ph0 = eng.phase_budget()
    sp0 = eng.speculation_stats()
    ms0 = eng.memory_stats()
    pg0 = eng.paging_stats()
    sc0 = eng.scheduler_stats()
    pf0 = eng.perf_stats()
    ev0, dr0 = rec.events_total(), rec.dropped_events
    m0 = time.time()
    time.sleep(measure_s)
    with eng.stats_lock:
        tok1, err1 = eng.total_tokens, eng.total_errors
        fin1, ftok1 = eng.finished_requests, eng.finished_tokens
    ph1 = eng.phase_budget()
    sp1 = eng.speculation_stats()
    ms1 = eng.memory_stats()
    pg1 = eng.paging_stats()
    sc1 = eng.scheduler_stats()
    pf1 = eng.perf_stats()
    ev1, dr1 = rec.events_total(), rec.dropped_events
    m1 = time.time()
    # engine-loop budget over the window: where each wall-clock second of
    # the serve loop went (fetch = device round wait, dispatch = staging,
    # admit/prefill = admission work, emit = tokenizer+SSE queue puts,
    # idle = no work; the remainder is untimed loop overhead)
    wall = max(m1 - m0, 1e-9)
    phase_pct = {
        k: round(100.0 * (ph1[k] - ph0[k]) / wall, 1) for k in ph1
    }
    print(f"# serve phase budget (% of window wall): {phase_pct}", flush=True)
    # settle BEFORE stopping: requests POSTed near the window end whose first
    # delta is still pending are exactly the tail the p95 must capture —
    # cutting here would right-censor the percentiles low. Scaled so tiny
    # CPU smokes don't pay the full 8B-tail allowance.
    time.sleep(min(8.0, max(1.0, measure_s)))
    for p in procs:
        p.terminate()
    # ENGINE-DIRECT window on the same engine, same workload shape, no
    # HTTP/SSE in the loop: quantifies the serving-layer tax as a ratio in
    # every bench run (round-3 left it as two numbers measured hours apart).
    direct_tps = 0.0
    try:
        if not measure_direct:
            raise _SkipDirect
        # drain: terminated clients leave up to max_slots requests mid-
        # decode; their tokens must not leak into the direct window (and
        # their slots would starve direct admissions)
        drain_deadline = time.time() + 90.0
        while eng.slots_in_use() > 0 and time.time() < drain_deadline:
            time.sleep(0.25)

        # suffix sized like client_proc's (~60 bytes) so direct prompts land
        # in the SAME admission bucket the serve warmup compiled — a fresh
        # bucket's first compile inside this short window would deflate it
        def direct_prompt(i: int, r: int) -> str:
            return prompt + f" direct client {i} round {r}, answer briefly now?"

        stop_at = time.time() + max(8.0, measure_s / 3)

        def direct_client(i: int) -> None:
            r = 0
            while time.time() < stop_at:
                eng.generate(
                    direct_prompt(i, r), max_tokens=max_tokens, temperature=0.8
                )
                r += 1

        eng.generate(direct_prompt(0, -1), max_tokens=4, temperature=0.8)  # warm
        with eng.stats_lock:
            d_tok0 = eng.total_tokens
        d_t0 = time.time()
        dthreads = [
            threading.Thread(target=direct_client, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        for t in dthreads:
            t.start()
        for t in dthreads:
            t.join(timeout=measure_s * 3 + 60)
        with eng.stats_lock:
            d_tok1 = eng.total_tokens
        direct_tps = (d_tok1 - d_tok0) / max(time.time() - d_t0, 1e-6)
    except _SkipDirect:
        pass
    except Exception as e:  # never lose the serve window to the extra probe
        print(f"# engine-direct window failed: {e!r}", flush=True)
    with lock:
        ttfts = [
            (first - t0) * 1000.0
            for t0, first in ttft_records
            if m0 <= t0 <= m1
        ]
    # prefix-cache effectiveness: the serve workload's shared preamble should
    # be riding the prompt-prefix KV cache — a zero hit count here means the
    # headline is paying full prefill per request (diagnosis, not a gate)
    pstats = eng.prefix_cache_stats()
    # end-of-run ledger audit: sampled AFTER the direct window drained its
    # requests, so a nonzero count is a real refcount bug, not live traffic
    pg_end = eng.paging_stats()
    # latency waterfall ledger: sampled before shutdown tears the engine
    # down (the del below drops the reference the stats hang off)
    wf_end = eng.waterfall_stats()
    srv.shutdown()
    eng.shutdown()
    # Drop every reference to the engine's device buffers (8B weights + KV)
    # before returning: the caller may immediately build another model, and
    # two 8B footprints do not fit one 16 GB chip.
    del eng, srv
    gc.collect()
    out = {"tok_per_s": (tok1 - tok0) / (m1 - m0)}
    out["phase_pct"] = phase_pct
    if direct_tps > 0:
        out["engine_direct_tok_per_s"] = direct_tps
        eff = serve_efficiency(out)
        if eff is not None:
            out["serve_efficiency"] = eff
    out["prefix_cache_hits"] = float(pstats.get("hits", 0))
    out["prefix_cache_misses"] = float(pstats.get("misses", 0))
    # self-speculative decoding over the measurement window (deltas of the
    # engine's lifetime counters): accept_rate = accepted drafts ÷ drafted,
    # tok_per_call = tokens emitted per verify dispatch (1.0 would mean the
    # verify pass degenerated into plain decode)
    if sp0.get("enabled"):
        drafted = sp1["drafted_tokens"] - sp0["drafted_tokens"]
        accepted = sp1["accepted_tokens"] - sp0["accepted_tokens"]
        emitted = sp1["emitted_tokens"] - sp0["emitted_tokens"]
        calls = sp1["verify_calls"] - sp0["verify_calls"]
        out["spec_accept_rate"] = accepted / drafted if drafted > 0 else 0.0
        out["spec_tok_per_call"] = emitted / calls if calls > 0 else 0.0
        out["spec_verify_calls"] = float(calls)
    # KV-pool churn over the window (deltas of the pool's lifetime
    # counters), only when TPU_KV_HOST_OFFLOAD armed a pool: how many
    # preempt/restore cycles and admission sheds the window absorbed
    if ms0.get("enabled"):
        out["kv_preempted"] = ms1["preempted_total"] - ms0["preempted_total"]
        out["kv_restored"] = ms1["restored_total"] - ms0["restored_total"]
        out["kv_shed"] = ms1["shed_total"] - ms0["shed_total"]
        out["kv_headroom_end"] = ms1.get("headroom", 1.0)
        with lock:
            window_sheds = [d for t, d in shed_records if m0 <= t <= m1]
        out["kv_client_shed_429"] = float(len(window_sheds))
        if window_sheds:
            out["kv_retry_after_max_s"] = max(window_sheds)
    # Degenerate-window evidence (a run where decode is broken still serves
    # prefill first-tokens at a plausible-looking rate — VERDICT r2 recorded
    # 26 tok/s of pure first-tokens as the metric of record):
    # prefill economy over the window (scheduler true-vs-padded token
    # counters + the compile ledger): true prompt tok/s, the pad-waste the
    # staging shape cost on top of them, and how many distinct prefill
    # executables the run minted — the ragged path's whole thesis is the
    # last two numbers going down while the first goes up
    pf_true = sc1.get("prefill_true_tokens", 0.0) - sc0.get(
        "prefill_true_tokens", 0.0
    )
    pf_padded = sc1.get("prefill_padded_tokens", 0.0) - sc0.get(
        "prefill_padded_tokens", 0.0
    )
    if pf_padded > 0:
        out["prefill_tok_per_s"] = round(pf_true / wall, 1)
        out["prefill_pad_waste_pct"] = round(
            100.0 * (1.0 - pf_true / pf_padded), 1
        )
    from llm_mcp_tpu.telemetry.recorder import get_compile_ledger

    _PREFILL_PHASES = ("chunk", "pf_rag", "fused", "fused_rag")
    out["prefill_executables"] = float(
        sum(
            1
            for row in get_compile_ledger().table()
            if row.get("phase") in _PREFILL_PHASES
        )
    )
    out["window_errors"] = float(err1 - err0)
    finished = fin1 - fin0
    if finished > 0:
        out["mean_completion_tokens"] = (ftok1 - ftok0) / finished
    out["window_finished"] = float(finished)
    # paged-KV block economy: peak sharing ratio (logical/physical blocks)
    # is the admission multiplier the shared-prompt oversubscription sweep
    # gates at >= 3.0; COW copies are normalized per finished request; the
    # leak count is the end-of-run ledger audit — perf_gate hard-fails on
    # any nonzero value, no baseline leniency
    out["paged_sharing_peak"] = pg1.get("peak_sharing_ratio", 1.0)
    cow = pg1.get("cow_copies_total", 0.0) - pg0.get("cow_copies_total", 0.0)
    out["paged_cow_copies"] = cow
    if finished > 0:
        out["cow_copies_per_req"] = cow / finished
    out["paged_block_leaks"] = float(pg_end.get("leaks", 0.0))
    # physical block-pool HBM accounting (engine._phys_note_hbm): peak
    # contiguous-equivalent ÷ physically-resident KV bytes over the run —
    # the honest "how much HBM did sharing actually save" number (absent
    # when TPU_PAGED_PHYSICAL gated physical mode off)
    if pg_end.get("physical", 0.0):
        out["paged_hbm_bytes_ratio"] = pg_end.get("hbm_bytes_ratio_peak", 1.0)
        out["paged_hbm_bytes_physical"] = pg_end.get(
            "hbm_bytes_physical_peak", 0.0
        )
        out["paged_hbm_bytes_contiguous_equiv"] = pg_end.get(
            "hbm_bytes_contiguous_equiv_peak", 0.0
        )
    # flight-recorder cost over the window (telemetry/recorder.py): how many
    # step events the serve path appended, how many were dropped during dump
    # freezes (must stay 0 — perf_gate hard-fails on any), and the appends'
    # share of window wall priced by a measured per-event cost (<1% bar)
    out["recorder_events"] = float(ev1 - ev0)
    out["recorder_dropped_events"] = float(dr1 - dr0)
    per_ev = recorder_append_cost_s()
    out["recorder_events_per_s"] = round(1.0 / per_ev, 0) if per_ev > 0 else 0.0
    out["recorder_overhead_pct"] = round(100.0 * (ev1 - ev0) * per_ev / wall, 4)
    # perf observatory over the window (telemetry/perf.py): per-token ITL
    # percentiles (the rolling window at the m1 edge — freshly the window's
    # tokens), SLO-conforming goodput tok/s by delta of the lifetime
    # good-token ledger, and the live roofline MBU/MFU for the engine's
    # active cache layout from the sampled decode device walls
    itl1 = pf1.get("itl") or {}
    itl_n = itl1.get("samples", 0.0) - (pf0.get("itl") or {}).get("samples", 0.0)
    if itl_n > 0:
        out["itl_p50_ms"] = round(itl1.get("p50_ms", 0.0), 3)
        out["itl_p95_ms"] = round(itl1.get("p95_ms", 0.0), 3)
        out["itl_p99_ms"] = round(itl1.get("p99_ms", 0.0), 3)
        out["itl_samples"] = float(itl_n)
    gp0, gp1 = pf0.get("goodput") or {}, pf1.get("goodput") or {}
    if gp1.get("finished_tokens", 0.0) > gp0.get("finished_tokens", 0.0):
        out["goodput_tok_per_s"] = round(
            (gp1.get("good_tokens", 0.0) - gp0.get("good_tokens", 0.0)) / wall, 1
        )
        fin_tok = gp1.get("finished_tokens", 0.0) - gp0.get("finished_tokens", 0.0)
        good_tok = gp1.get("good_tokens", 0.0) - gp0.get("good_tokens", 0.0)
        out["goodput_ratio"] = round(good_tok / fin_tok, 4) if fin_tok else 1.0
    rl1 = pf1.get("roofline") or {}
    if rl1.get("device_tok_per_s", 0.0) > 0:
        out["decode_mfu"] = rl1.get("decode_mfu", 0.0)
        out["decode_mbu"] = rl1.get("decode_mbu", 0.0)
        out["perf_device_tok_per_s"] = rl1.get("device_tok_per_s", 0.0)
    if ttfts:
        out["p50_ttft_ms"] = statistics.median(ttfts)
        out["p95_ttft_ms"] = sorted(ttfts)[max(0, int(len(ttfts) * 0.95) - 1)]
        out["ttft_samples"] = float(len(ttfts))
    # latency waterfall over the run (telemetry/workload.py): per-stage
    # p95s of the exact wall partition — where a finished request's time
    # actually went, beside the TTFT/ITL aggregates above
    ws = wf_end
    if ws.get("requests", 0):
        out["waterfall_coverage"] = ws.get("coverage", 1.0)
        for stage in ("admit_wait", "prefill_queue", "prefill_compute",
                      "decode", "stall"):
            out[f"waterfall_{stage}_p95_ms"] = (
                (ws.get("stages") or {}).get(stage, {}).get("p95_ms", 0.0)
            )
        out["waterfall_total_p95_ms"] = ws.get("total_p95_ms", 0.0)
    return out


def embed_path_metrics(
    model: str,
    *,
    batch: int,
    dimensions: int = 0,
    measure_s: float = 15.0,
    max_batch: int = 64,
    max_seq_len: int = 512,
    quant: str = "",
    concurrency: int = 1,
) -> dict[str, float]:
    """embeds/s and p50 request latency through the REAL
    `POST /v1/embeddings` path (BASELINE configs #1 nomic single-input and
    #4 qwen3-embedding-8b batch-64 dimensions=1024 — the embed half of the
    metric of record that had never produced a number; reference measures
    via benchmark.ollama.embed jobs, worker/llm_worker/main.py:471-518).

    With `concurrency=1` requests run sequentially from one client: the
    engine batches internally, and embed latency (one forward) is the
    object of interest. `concurrency>1` runs that many HTTP clients
    looping concurrently — the engine's internal batcher coalesces the
    simultaneous posts, so the aggregate embeds/s is the serving-path
    throughput an operator actually sees (p50 then includes queueing)."""
    import statistics
    import threading
    import urllib.request

    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.api.server import CoreServer
    from llm_mcp_tpu.executor import EmbeddingEngine
    from llm_mcp_tpu.state.db import Database
    from llm_mcp_tpu.utils.config import Config

    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    eng = EmbeddingEngine(
        model, max_batch=max_batch, max_seq_len=max_seq_len, dtype=dtype, quant=quant
    )
    srv = CoreServer(
        Config(), db=Database(":memory:"), gen_engines={}, embed_engines={model: eng}
    ).start("127.0.0.1", 0)
    url = f"http://127.0.0.1:{srv.api.port}/v1/embeddings"
    texts = [
        f"embedding benchmark input {i}: the quick brown fox jumps over "
        f"the lazy dog near the riverbank at dawn" for i in range(batch)
    ]
    body: dict = {"model": model, "input": texts if batch > 1 else texts[0]}
    if dimensions:
        body["dimensions"] = dimensions
    payload = json.dumps(body).encode()

    def post() -> float:
        t0 = time.perf_counter()
        req = urllib.request.Request(
            url, data=payload, headers={"Content-Type": "application/json"}
        )
        with urllib.request.urlopen(req, timeout=600) as r:
            doc = json.loads(r.read())
        assert len(doc["data"]) == batch, len(doc["data"])
        if dimensions:
            assert len(doc["data"][0]["embedding"]) == dimensions
        return (time.perf_counter() - t0) * 1000.0

    breakdown: dict[str, float] = {}
    try:
        post()  # warm the (batch-bucket, seq-bucket) executable
        post()
        lats: list[float] = []
        n_embeds = 0
        llock = threading.Lock()
        t0 = time.perf_counter()

        def pump() -> None:
            nonlocal n_embeds
            while time.perf_counter() - t0 < measure_s:
                ms = post()
                with llock:
                    lats.append(ms)
                    n_embeds += batch

        if concurrency > 1:
            workers = [
                threading.Thread(target=pump, daemon=True)
                for _ in range(concurrency)
            ]
            for w in workers:
                w.start()
            for w in workers:
                w.join(timeout=measure_s * 4 + 600)
        else:
            pump()
        wall = time.perf_counter() - t0
        if batch == 1 and concurrency == 1:
            # Latency budget for the single-input case: record the
            # dispatch→fetch sync floor (identity kernel fetch) beside the
            # forward's own fetch, so the p50 separates what any device
            # round trip costs on this machine from the host's own work.
            import numpy as np

            # prepare_ids + _bucket reproduce the exact executable the p50
            # path dispatched — a different bucket is a different kernel
            ids = eng.prepare_ids(texts[0])
            bucket = eng._bucket(len(ids))
            toks = np.zeros((1, bucket), np.int32)
            toks[0, : len(ids)] = ids
            lens = np.asarray([len(ids)], np.int32)
            np.asarray(eng._fwd(eng.params, toks, lens))  # warm this bucket
            ident = jax.jit(lambda x: x + 1)
            z = jnp.zeros((1,), jnp.float32)
            np.asarray(ident(z))
            fwd_ms, floor_ms = [], []
            for _ in range(12):
                t1 = time.perf_counter()
                np.asarray(eng._fwd(eng.params, toks, lens))
                fwd_ms.append((time.perf_counter() - t1) * 1e3)
                t1 = time.perf_counter()
                np.asarray(ident(z))
                floor_ms.append((time.perf_counter() - t1) * 1e3)
            fwd_p50 = statistics.median(fwd_ms)
            breakdown["sync_floor_ms"] = statistics.median(floor_ms)
            breakdown["fwd_fetch_ms"] = fwd_p50
            breakdown["host_ms"] = max(statistics.median(lats) - fwd_p50, 0.0)
    finally:
        # a failed sweep must not leave the engine's weights resident — the
        # 8B serve headline runs after this on the same 16 GB chip
        srv.shutdown()
        del eng, srv
        gc.collect()
    return {
        "embeds_per_s": n_embeds / wall,
        "p50_ms": statistics.median(lats),
        "n_requests": float(len(lats)),
        **breakdown,
    }


def serve_window_degenerate(
    serve: dict[str, float], max_tokens: int, raw_error: bool
) -> str:
    """Why a serve window must NOT become the metric of record ('' = fine).

    A broken decode path still completes prefills and emits exactly one
    sampled token per request, so 'tok/s >= 1' is no guard at all. Refuse
    the window when the engine errored requests inside it, when finished
    requests averaged < max_tokens/4 completion tokens (healthy clients all
    run to max_tokens — eos on random-init weights is ~never sampled; a real
    checkpoint's early-stop still clears a quarter), or when the raw decode
    sweep crashed in this same process (same kernels, same bug) AND the
    serve window carries no completion evidence of its own — a window that
    demonstrably ran full completions stands on its own merits (the raw
    sweep's B=112 config can OOM-fail for reasons serve's B=80 never hits,
    and run_raw's contract is that its failure must not eat the bench line)."""
    if raw_error and serve.get("window_finished", 0.0) <= 0:
        return "raw decode sweep errored and the window finished no requests"
    if serve.get("window_errors", 0.0) > 0:
        return f"{int(serve['window_errors'])} requests errored in the window"
    mean_done = serve.get("mean_completion_tokens")
    if mean_done is not None and mean_done < max_tokens / 4:
        return (
            f"finished requests averaged {mean_done:.1f} completion tokens"
            f" (< max_tokens/4 = {max_tokens / 4:.0f}: decode is not running)"
        )
    return ""


def _arm_deadline(seconds: float, what: str) -> "threading.Timer":
    """Hard-exit (rc=3) if `seconds` elapse: a bench that overruns its budget
    must end with an rc and a diagnostic line, not run on in silence."""
    import threading

    def boom() -> None:
        print(f"# bench DEADLINE EXCEEDED ({what} > {seconds:.0f}s); aborting",
              flush=True)
        _exit_now(3)

    t = threading.Timer(seconds, boom)
    t.daemon = True
    t.start()
    return t


def main() -> None:
    from llm_mcp_tpu.utils.config import enable_compile_cache

    # one rule for the compile cache (JAX_COMPILATION_CACHE_DIR where set,
    # else <checkout>/.jax_cache): a first compile of a rare executable
    # shape landing INSIDE a measured serve window distorts it more than
    # anything else the harness controls
    enable_compile_cache()
    platform = device_platform()
    if platform != "tpu":
        # a rate from the CPU backend is nobody's number: the measurement
        # path fails without a chip (the correctness legs the tests import
        # — trace_replay_metrics, capture_replay_smoke, ... — are functions
        # and stay importable)
        raise SystemExit(
            f"bench.py measures on the chip; JAX reports platform={platform!r}"
        )
    deadline_s = float(os.environ.get("BENCH_DEADLINE_S", "3600"))
    _arm_deadline(deadline_s, "total bench")
    t_bench0 = time.time()

    def over_budget(share: float, what: str, marker: str) -> bool:
        """Secondary sweeps yield to the serve HEADLINE (which runs last):
        once `share` of the deadline is spent, remaining secondaries skip
        loudly — with a machine-readable marker, so a vanished metric key
        reads as 'skipped for time', never as silent loss."""
        if time.time() - t_bench0 > share * deadline_s:
            print(f"# skipping {what}: {share:.0%} of BENCH_DEADLINE_S spent",
                  flush=True)
            secondary[marker] = 1.0
            return True
        return False
    on_tpu = platform == "tpu"  # always, past the guard above

    if os.environ.get("BENCH_TRACE"):
        # deterministic trace replay as the line of record: re-issue a
        # captured (or synth:<kind>:<n>[:seed]) workload open-loop with
        # faithful inter-arrival gaps / BENCH_TRACE_COMPRESS, seeded by
        # BENCH_TRACE_SEED so two runs issue byte-identical streams
        src = os.environ["BENCH_TRACE"]
        model = os.environ.get("BENCH_MODEL") or (
            "llama-3.1-8b" if on_tpu else "tiny-llm"
        )
        rp = trace_replay_metrics(
            src, model=model,
            max_slots=int(os.environ.get("BENCH_B") or (112 if on_tpu else 4)),
            max_seq_len=int(os.environ.get("BENCH_S") or (2048 if on_tpu else 512)),
            decode_chunk=8 if on_tpu else 4,
            quant="int8" if on_tpu else "",
            kv_quant="int8" if on_tpu else "",
            max_tokens_cap=0 if on_tpu else 16,
        )
        line = {
            "metric": f"replay_tok_per_s_{model}_{platform}",
            "value": rp.pop("replay_tok_per_s", 0.0),
            "unit": "tok/s",
            "vs_baseline": 0.0,
            **{k: v for k, v in rp.items() if k != "outputs"},
        }
        print(json.dumps(line))
        return

    if os.environ.get("BENCH_MODEL"):
        model = os.environ["BENCH_MODEL"]
        B = int(os.environ.get("BENCH_B", "32"))
        S = int(os.environ.get("BENCH_S", "1024"))
        K = int(os.environ.get("BENCH_K", "64"))
        kv8 = os.environ.get("BENCH_KV", "") == "int8"
        tps = raw_decode_tps(model, B, S, K, rounds=4 if on_tpu else 2, kv_int8=kv8)
        kv = "_kv8" if kv8 else ""
        print(
            json.dumps(
                {
                    "metric": f"decode_tok_per_s_{model}-int8{kv}_b{B}_{platform}",
                    "value": round(tps, 1),
                    "unit": "tok/s/chip",
                    "vs_baseline": round(tps / 2000.0, 3),
                }
            )
        )
        return

    secondary: dict[str, float] = {}
    serve: dict[str, float] = {}
    if on_tpu:
        # Headline: the baseline's own model and the baseline's own metric —
        # tok/s/chip + p50 TTFT through /v1/chat/completions SSE (BASELINE.md
        # line 28), int8 weights + int8 KV on one v5e chip. The raw jitted
        # decode loop (same program minus the serving stack) is reported as
        # secondary so the engine's host-side overhead stays visible.
        model, B, S = "llama-3.1-8b", int(os.environ.get("BENCH_SLOTS", "80")), 1024

        def run_raw() -> float:
            """The 8B raw-decode sweep — defined once so the secondary and
            the fallback headline can never drift apart."""
            tps = 0.0
            try:
                st: dict[str, float] = {}
                tps = round(
                    raw_decode_tps(model, 112, S, 64, rounds=4, kv_int8=True, stats=st),
                    1,
                )
                secondary[f"raw_decode_tok_per_s_{model}-int8_kv8_b112_{platform}"] = tps
                if st.get("weight_bytes"):
                    # achieved weight-stream bandwidth of the layer pass: the
                    # batch shares one weight read per step, so GB/s =
                    # weight bytes x (tok rate / B). r05 measured ~570 of the
                    # v5e's 819 GB/s; the wqkv/w13 fusion + scan unroll target
                    # 650+ (scripts/kernel_bench.py re-measures at any shape)
                    secondary["layers_gbps"] = round(
                        st["weight_bytes"] * (tps / 112) / 1e9, 1
                    )
            except Exception as e:  # a failure must not eat the bench line
                print(f"# raw-decode sweep failed: {e!r}", flush=True)
                secondary["raw_decode_error"] = 0.0
            gc.collect()
            try:
                # attention-dispatch microbench at the headline shape: µs per
                # DMA cell of the fused blocked q8 arm (scripts/kernel_bench
                # is the sweep tool; this single point rides the bench record
                # so cross-round drift in per-cell overhead is visible)
                import importlib.util as _ilu

                _kb_spec = _ilu.spec_from_file_location(
                    "kernel_bench",
                    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "scripts", "kernel_bench.py"),
                )
                _kb = _ilu.module_from_spec(_kb_spec)
                _kb_spec.loader.exec_module(_kb)
                pt = _kb.bench_attn("q8_gqa", 112, S, 0.5, arm="blocked", iters=10)
                secondary["attn_us_per_cell"] = pt["attn_us_per_cell"]
                secondary["attn_dma_per_cell"] = float(pt["dma_per_cell"])
                # same point through the block-indirect gather (half of
                # every row's blocks table-redirected to the pool): the
                # per-cell price of physical paging at the headline shape
                pp = _kb.bench_attn("q8_gqa", 112, S, 0.5, arm="paged", iters=10)
                secondary["attn_us_per_cell_paged"] = pp["attn_us_per_cell"]
            except Exception as e:
                print(f"# attn microbench failed: {e!r}", flush=True)
                secondary["attn_cell_error"] = 0.0
            gc.collect()  # drop the B=112 sweep's weights+cache before re-building
            # run even when the B=112 sweep failed: the small B=8 config can
            # survive an OOM that killed the big one, and it is the only
            # on-hardware exercise of the blocked kernel
            if os.environ.get("BENCH_LONG_S", "1") != "0" and not over_budget(
                0.25, "long-context sweep", "raw_long_s_skipped"
            ):
                # long-context decode on the real chip: S=8192 routes through
                # the BLOCKED q8 kernel (manual-DMA double buffering, dynamic
                # trip count — kernels/attention.py:_attend_q8_blocked_kernel),
                # so the driver's artifact exercises the path CPU tests can
                # only reach in interpret mode (VERDICT r2 weak #4)
                try:
                    lt = round(
                        raw_decode_tps(model, 8, 8192, 32, rounds=2, kv_int8=True), 1
                    )
                    secondary[f"raw_decode_tok_per_s_{model}-int8_kv8_b8_s8192_{platform}"] = lt
                except Exception as e:
                    print(f"# long-context raw sweep failed: {e!r}", flush=True)
                    secondary["raw_long_s_error"] = 0.0
            gc.collect()  # each sweep below re-builds a ~14 GB model
            if os.environ.get("BENCH_MLA", "1") != "0" and not over_budget(
                0.35, "mla long-context sweep", "raw_mla_skipped"
            ):
                # MLA latent-cache long context (models/mla.py): 4 slots x
                # 32k context costs ~4.8 GB of bf16 latents (576 values x
                # 2 B x 32 layers) beside ~9.3 GB of int8 weights — 14 GB
                # on the 16 GB chip. The GQA 8B config's bf16 KV at the
                # same (B, S) would be ~8.6 GB (3.6x the values); its int8
                # KV ~4.4 GB. (int8 latents exist too — kv_quant=int8 —
                # trading a dequant-then-dot for another 2x capacity.)
                try:
                    mt = round(
                        raw_decode_tps("mla-8b", 4, 32_768, 32, rounds=2), 1
                    )
                    secondary[f"raw_decode_tok_per_s_mla-8b-int8_b4_s32768_{platform}"] = mt
                except Exception as e:
                    print(f"# mla long-context sweep failed: {e!r}", flush=True)
                    secondary["raw_mla_error"] = 0.0
                gc.collect()
                try:
                    # int8 LATENTS at 32k: the blocked s8-MXU kernel
                    # (kernels/attention.py:_attend_q8_mla_blocked_kernel)
                    # — half the cache bytes of the bf16 sweep above and
                    # measured faster (r5: 199 vs 161 tok/s)
                    mt8 = round(
                        raw_decode_tps(
                            "mla-8b", 4, 32_768, 32, rounds=2, kv_int8=True
                        ), 1,
                    )
                    secondary[
                        f"raw_decode_tok_per_s_mla-8b-int8_kv8_b4_s32768_{platform}"
                    ] = mt8
                except Exception as e:
                    print(f"# mla kv8 long-context sweep failed: {e!r}", flush=True)
                    secondary["raw_mla_kv8_s32k_error"] = 0.0
                gc.collect()
                # int8 LATENTS at serving shapes: S=2048 fits the whole-S
                # s8-MXU MLA kernel (decode_attend_q8_mla) — this sweep is
                # its on-hardware evidence; the kv8 S=32768 sweep above is
                # the BLOCKED s8 kernel's
                try:
                    mk = round(
                        raw_decode_tps("mla-8b", 32, 2048, 32, rounds=2,
                                       kv_int8=True), 1
                    )
                    secondary[f"raw_decode_tok_per_s_mla-8b-int8_kv8_b32_s2048_{platform}"] = mk
                except Exception as e:
                    print(f"# mla kv8 kernel sweep failed: {e!r}", flush=True)
                    secondary["raw_mla_kv8_error"] = 0.0
                gc.collect()
            return tps

        # raw loop FIRST: it frees cleanly on return, while the serve run's
        # HTTP threads can pin engine buffers past shutdown — running the 8B
        # raw sweep after the serve engine reliably OOMs a 16 GB chip
        raw_tps = 0.0
        raw_attempted = False
        if os.environ.get("BENCH_SECONDARY", "1") != "0":
            raw_attempted = True
            raw_tps = run_raw()
            gc.collect()
        if os.environ.get("BENCH_EMBED", "1") != "0" and not over_budget(
            0.45, "embed sweeps", "embed_skipped"
        ):
            # BASELINE embed configs (#1 and #4): the embed half of the
            # metric of record ("embeds/sec at batch-64", BASELINE.json)
            try:
                em = embed_path_metrics("nomic-embed-text", batch=1, measure_s=10.0)
                secondary[f"embed_per_s_nomic-embed-text_b1_{platform}"] = round(
                    em["embeds_per_s"], 1
                )
                secondary["embed_p50_ms_nomic-embed-text_b1"] = round(em["p50_ms"], 1)
                if "sync_floor_ms" in em:
                    # p50 ≈ sync_floor (one device round trip) + host_ms
                    # (framework)
                    secondary["embed_b1_sync_floor_ms"] = round(em["sync_floor_ms"], 1)
                    secondary["embed_b1_host_ms"] = round(em["host_ms"], 1)
            except Exception as e:
                print(f"# nomic embed sweep failed: {e!r}", flush=True)
                secondary["embed_nomic_error"] = 0.0
            gc.collect()
            try:
                # the b64 config runs under HTTP concurrency: batch-64 bodies
                # posted by several clients at once is what a production
                # retrieval indexer actually sends, and the engine batcher's
                # coalescing only shows up with simultaneous requests in it
                embed_conc = max(1, int(os.environ.get("BENCH_EMBED_CONC", "4")))
                em = embed_path_metrics(
                    "qwen3-embedding-8b", batch=64, dimensions=1024,
                    measure_s=20.0, quant="int8", concurrency=embed_conc,
                )
                secondary[f"embed_per_s_qwen3-embedding-8b-int8_b64_d1024_{platform}"] = (
                    round(em["embeds_per_s"], 1)
                )
                secondary["embed_p50_ms_qwen3-embedding-8b-int8_b64"] = round(
                    em["p50_ms"], 1
                )
                secondary["embed_qwen3_b64_http_concurrency"] = float(embed_conc)
            except Exception as e:
                print(f"# qwen3-embedding-8b sweep failed: {e!r}", flush=True)
                secondary["embed_qwen3_error"] = 0.0
            gc.collect()
        bench_max_tokens = int(os.environ.get("BENCH_MAX_TOKENS", "256"))
        # 16 beat 32 on BOTH axes in the r4 hardware sweep (2524.8 tok/s @
        # p50 TTFT 1306 ms vs 2428 @ 2004): shorter rounds admit waiting
        # prompts sooner AND lose less work to the final partial round. The
        # post-headline sweep below measures the complementary chunk so the
        # trade stays visible run to run.
        headline_chunk = int(os.environ.get("BENCH_DECODE_CHUNK", "16"))
        alt_chunk = 32 if headline_chunk <= 16 else 16
        if os.environ.get("BENCH_SERVE", "1") != "0":
            # one retry: a transient chip hiccup can zero a whole window, and
            # a silently-recorded 0.0 would corrupt the metric of record
            for attempt in (1, 2):
                try:
                    serve = serve_path_metrics(
                        model,
                        n_clients=B,
                        max_tokens=bench_max_tokens,
                        measure_s=float(os.environ.get("BENCH_MEASURE_S", "30")),
                        max_slots=B,
                        max_seq_len=S,
                        decode_chunk=headline_chunk,
                        # 8 measured better p50 TTFT than 4 at B=80 (2286 vs
                        # 2645 ms) at equal throughput: fewer, larger fused
                        # admissions amortize the prompt weight pass
                        admit_batch=int(os.environ.get("BENCH_ADMIT_BATCH", "8")),
                        decode_compact=os.environ.get("BENCH_DECODE_COMPACT", "auto"),
                    )
                except Exception as e:  # never lose the bench line to a serve bug
                    secondary["serve_path_error"] = 0.0
                    print(f"# serve-path bench failed: {e!r}", flush=True)
                    break
                if serve.get("tok_per_s", 0.0) >= 1.0:
                    break
                serve = {}
                # a retry may still OOM if the failed run's HTTP threads pin
                # engine buffers — the except above then records the error
                secondary["serve_path_zero_windows"] = float(attempt)
                print(
                    f"# serve-path attempt {attempt} measured ~0 tok/s"
                    + ("; retrying" if attempt == 1 else "; falling back to raw"),
                    flush=True,
                )
                gc.collect()
        if serve:
            # A window can "succeed" at a plausible rate with decode 100%
            # broken (prefill first-tokens only). Refuse it loudly: the raw
            # sweep becomes the headline if it ran; otherwise hard-fail so
            # the driver records rc != 0 instead of a quiet garbage number.
            reason = serve_window_degenerate(
                serve, bench_max_tokens, "raw_decode_error" in secondary
            )
            if reason:
                print(f"# serve window DEGENERATE ({reason}); refusing headline",
                      flush=True)
                secondary["serve_degenerate_tok_per_s"] = round(
                    serve.get("tok_per_s", 0.0), 1
                )
                serve = {}
        # BENCH_TTFT_K16 is the r3/r4 name for the same opt-out; honor both
        alt_enabled = (
            os.environ.get("BENCH_TTFT_ALT", os.environ.get("BENCH_TTFT_K16", "1"))
            != "0"
        )
        if serve and alt_enabled and not over_budget(
            0.75, f"K={alt_chunk} sweep", f"ttft_k{alt_chunk}_skipped"
        ):
            # Decode-chunk trade sweep: run a second, shorter serve window
            # at the complementary chunk so throughput-vs-TTFT stays
            # measured on hardware in the same run as the headline (r4
            # evidence: 16 beat 32 on both axes; keep checking).
            try:
                alt = serve_path_metrics(
                    model,
                    n_clients=B,
                    max_tokens=bench_max_tokens,
                    measure_s=min(
                        20.0, float(os.environ.get("BENCH_MEASURE_S", "30"))
                    ),
                    max_slots=B,
                    max_seq_len=S,
                    decode_chunk=alt_chunk,
                    admit_batch=int(os.environ.get("BENCH_ADMIT_BATCH", "8")),
                    decode_compact=os.environ.get("BENCH_DECODE_COMPACT", "auto"),
                    measure_direct=False,
                )
                if alt.get("tok_per_s", 0.0) >= 1.0:
                    secondary[f"serve_tok_per_s_k{alt_chunk}"] = round(
                        alt["tok_per_s"], 1
                    )
                    secondary[f"serve_p50_ttft_ms_k{alt_chunk}"] = round(
                        alt.get("p50_ttft_ms", -1.0), 1
                    )
                    secondary[f"serve_p95_ttft_ms_k{alt_chunk}"] = round(
                        alt.get("p95_ttft_ms", -1.0), 1
                    )
                else:
                    # distinguish "ran but degenerate" from "never ran"
                    secondary[f"ttft_k{alt_chunk}_zero_window"] = round(
                        alt.get("tok_per_s", 0.0), 1
                    )
                    print(f"# K={alt_chunk} sweep window degenerate; not recorded",
                          flush=True)
            except Exception as e:
                print(f"# K={alt_chunk} sweep failed: {e!r}", flush=True)
                secondary[f"ttft_k{alt_chunk}_error"] = 0.0
            gc.collect()
        if serve and os.environ.get("BENCH_SPEC", "1") != "0" and not over_budget(
            0.8, "speculation sweep", "spec_sweep_skipped"
        ):
            # Self-speculative payoff sweep: the SAME repetitive greedy
            # workload (loop-heavy completions, the n-gram drafter's best
            # case) with draft-and-verify on vs TPU_SPEC=0, so the verify
            # pass's cost/benefit stays measured on hardware every run —
            # the spec config's tok/s must not fall below the plain one.
            spec_win = min(20.0, float(os.environ.get("BENCH_MEASURE_S", "30")))

            def _rep_window() -> dict:
                return serve_path_metrics(
                    model,
                    n_clients=B,
                    max_tokens=bench_max_tokens,
                    measure_s=spec_win,
                    max_slots=B,
                    max_seq_len=S,
                    decode_chunk=headline_chunk,
                    admit_batch=int(os.environ.get("BENCH_ADMIT_BATCH", "8")),
                    decode_compact=os.environ.get("BENCH_DECODE_COMPACT", "auto"),
                    measure_direct=False,
                    workload="repetitive",
                )

            try:
                rep = _rep_window()
                gc.collect()
                # engines read TPU_SPEC at construction; flip it only around
                # the comparison window, restoring whatever was set before
                prior_spec = os.environ.get("TPU_SPEC")
                os.environ["TPU_SPEC"] = "0"
                try:
                    base = _rep_window()
                finally:
                    if prior_spec is None:
                        os.environ.pop("TPU_SPEC", None)
                    else:
                        os.environ["TPU_SPEC"] = prior_spec
                if rep.get("tok_per_s", 0.0) >= 1.0:
                    secondary["serve_spec_tok_per_s"] = round(rep["tok_per_s"], 1)
                    secondary["spec_accept_rate"] = round(
                        rep.get("spec_accept_rate", 0.0), 3
                    )
                    secondary["spec_tok_per_call"] = round(
                        rep.get("spec_tok_per_call", 0.0), 2
                    )
                if base.get("tok_per_s", 0.0) >= 1.0:
                    secondary["serve_nospec_tok_per_s"] = round(
                        base["tok_per_s"], 1
                    )
            except Exception as e:
                print(f"# speculation sweep failed: {e!r}", flush=True)
                secondary["spec_sweep_error"] = 0.0
            gc.collect()
        if serve and os.environ.get("BENCH_OVERSUB", "1") != "0" and not over_budget(
            0.82, "oversubscription sweep", "oversub_skipped"
        ):
            # 2x slot oversubscription through the KV pool: the headline's
            # B clients against B//2 slots with host offload armed. The
            # pool's three promises stay measured on hardware every run —
            # zero window errors (sheds are 429+Retry-After, which clients
            # honor and report as SHED, never failures), preempt/restore
            # churn bounded (counters land in the line of record), and an
            # admitted p95 TTFT that degrades boundedly vs uncontended.
            over_win = min(20.0, float(os.environ.get("BENCH_MEASURE_S", "30")))
            prior_offload = os.environ.get("TPU_KV_HOST_OFFLOAD")
            os.environ["TPU_KV_HOST_OFFLOAD"] = "1"
            try:
                over = serve_path_metrics(
                    model,
                    n_clients=B,
                    max_tokens=bench_max_tokens,
                    measure_s=over_win,
                    max_slots=max(1, B // 2),
                    max_seq_len=S,
                    decode_chunk=headline_chunk,
                    admit_batch=int(os.environ.get("BENCH_ADMIT_BATCH", "8")),
                    decode_compact=os.environ.get("BENCH_DECODE_COMPACT", "auto"),
                    measure_direct=False,
                )
                if over.get("tok_per_s", 0.0) >= 1.0:
                    secondary["oversub_tok_per_s"] = round(over["tok_per_s"], 1)
                    secondary["oversub_p95_ttft_ms"] = round(
                        over.get("p95_ttft_ms", -1.0), 1
                    )
                    secondary["oversub_window_errors"] = over.get(
                        "window_errors", 0.0
                    )
                    for k in ("kv_preempted", "kv_restored", "kv_shed",
                              "kv_client_shed_429"):
                        secondary["oversub_" + k] = over.get(k, 0.0)
                    if "kv_retry_after_max_s" in over:
                        secondary["oversub_retry_after_max_s"] = over[
                            "kv_retry_after_max_s"
                        ]
                else:
                    secondary["oversub_zero_window"] = round(
                        over.get("tok_per_s", 0.0), 1
                    )
                    print("# oversubscription sweep window degenerate; "
                          "not recorded", flush=True)
            except Exception as e:
                print(f"# oversubscription sweep failed: {e!r}", flush=True)
                secondary["oversub_error"] = 0.0
            finally:
                if prior_offload is None:
                    os.environ.pop("TPU_KV_HOST_OFFLOAD", None)
                else:
                    os.environ["TPU_KV_HOST_OFFLOAD"] = prior_offload
            gc.collect()
        if serve and os.environ.get("BENCH_PAGED", "1") != "0" and not over_budget(
            0.84, "paged shared-prefix sweep", "paged_skipped"
        ):
            # Paged-KV acceptance sweep: the headline's B clients, 90% of
            # them asking over ONE long shared preamble, against HALF the
            # slots with host offload armed — equal HBM budget, oversubbed
            # 2x. The refcounted block ledger should multiply admitted
            # capacity >= 3x (paged_admit_ratio, gated by perf_gate), copy
            # only boundary blocks on divergence (cow_copies_per_req <= 2),
            # and leak nothing (paged_block_leaks is an exact-zero gate).
            paged_win = min(20.0, float(os.environ.get("BENCH_MEASURE_S", "30")))
            prior_offload = os.environ.get("TPU_KV_HOST_OFFLOAD")
            os.environ["TPU_KV_HOST_OFFLOAD"] = "1"
            try:
                pg = serve_path_metrics(
                    model,
                    n_clients=B,
                    max_tokens=bench_max_tokens,
                    measure_s=paged_win,
                    max_slots=max(1, B // 2),
                    max_seq_len=S,
                    decode_chunk=headline_chunk,
                    admit_batch=int(os.environ.get("BENCH_ADMIT_BATCH", "8")),
                    decode_compact=os.environ.get("BENCH_DECODE_COMPACT", "auto"),
                    measure_direct=False,
                    workload="shared",
                )
                if pg.get("tok_per_s", 0.0) >= 1.0:
                    secondary["paged_tok_per_s"] = round(pg["tok_per_s"], 1)
                    secondary["paged_admit_ratio"] = round(
                        pg.get("paged_sharing_peak", 1.0), 2
                    )
                    secondary["paged_cow_copies"] = pg.get(
                        "paged_cow_copies", 0.0
                    )
                    if "cow_copies_per_req" in pg:
                        secondary["cow_copies_per_req"] = round(
                            pg["cow_copies_per_req"], 3
                        )
                    secondary["paged_block_leaks"] = pg.get(
                        "paged_block_leaks", 0.0
                    )
                    secondary["paged_shed"] = pg.get("kv_shed", 0.0)
                    secondary["paged_p95_ttft_ms"] = round(
                        pg.get("p95_ttft_ms", -1.0), 1
                    )
                    if "paged_hbm_bytes_ratio" in pg:
                        secondary["paged_hbm_bytes_ratio"] = round(
                            pg["paged_hbm_bytes_ratio"], 2
                        )
                        secondary["paged_hbm_bytes_physical_mb"] = round(
                            pg.get("paged_hbm_bytes_physical", 0.0) / 2**20, 1
                        )
                else:
                    secondary["paged_zero_window"] = round(
                        pg.get("tok_per_s", 0.0), 1
                    )
                    print("# paged shared-prefix sweep window degenerate; "
                          "not recorded", flush=True)
            except Exception as e:
                print(f"# paged shared-prefix sweep failed: {e!r}", flush=True)
                secondary["paged_sweep_error"] = 0.0
            finally:
                if prior_offload is None:
                    os.environ.pop("TPU_KV_HOST_OFFLOAD", None)
                else:
                    os.environ["TPU_KV_HOST_OFFLOAD"] = prior_offload
            gc.collect()
        if serve and os.environ.get("BENCH_MIGRATE", "1") != "0" and not over_budget(
            0.845, "migration sweep", "migrate_skipped"
        ):
            # 2-engine KV-migration sweep: the oversubscribed workload of
            # the pool sweep, but with an idle second replica the
            # MigrationCoordinator can drain into. perf_gate floors: at
            # least one snapshot/requeue actually moved (migration_count
            # >= 1) and the drained leg's admitted p95 TTFT no worse than
            # shedding-only (migrate_ttft_gain >= 1.0). Two replicas means
            # 2x weights resident — a quarter of the headline's clients
            # and short sequences keep the sweep inside one chip's HBM.
            try:
                mg = migration_sweep(
                    model,
                    n_clients=max(4, B // 4),
                    max_tokens=min(32, bench_max_tokens),
                    max_slots=max(1, B // 16),
                    max_seq_len=min(S, 1024),
                    decode_chunk=headline_chunk,
                    quant="int8", kv_quant="int8",
                )
                if "migrate_single_device" in mg:
                    secondary.update(mg)  # gated keys absent: [SKIP] + warn
                elif mg.get("migrate_requests", 0.0) >= 1.0:
                    secondary.update(mg)
                else:
                    secondary["migrate_zero_window"] = 0.0
                    print("# migration sweep window degenerate; not recorded",
                          flush=True)
            except Exception as e:
                print(f"# migration sweep failed: {e!r}", flush=True)
                secondary["migrate_sweep_error"] = 0.0
            gc.collect()
        if serve and os.environ.get("BENCH_PREFIX_ROUTE", "1") != "0" and \
                not over_budget(
                    0.85, "prefix routing sweep", "prefix_route_skipped"
                ):
            # 2-engine prefix-locality routing sweep: 90%-shared-prefix
            # workload through a real Router; perf_gate floor
            # prefix_route_hit_rate >= 0.5. Shared prefix of 320 tokens so
            # the fetch path clears the shipped 256-token minimum and the
            # crossover measurement speaks to the default.
            try:
                pr = prefix_routing_sweep(
                    model,
                    n_clients=max(4, B // 4),
                    rounds=3,
                    max_tokens=min(32, bench_max_tokens),
                    max_slots=max(2, B // 16),
                    max_seq_len=min(S, 1024),
                    decode_chunk=headline_chunk,
                    quant="int8", kv_quant="int8",
                    shared_tokens=320,
                    poisson_rps=bench_poisson_rps(),
                )
                if "prefix_route_single_device" in pr:
                    secondary.update(pr)  # gated keys absent: [SKIP] + warn
                elif pr.get("route_requests", 0.0) >= 1.0:
                    secondary.update(pr)
                else:
                    secondary["prefix_route_zero_window"] = 0.0
                    print("# prefix routing sweep window degenerate; not"
                          " recorded", flush=True)
            except Exception as e:
                print(f"# prefix routing sweep failed: {e!r}", flush=True)
                secondary["prefix_route_sweep_error"] = 0.0
            gc.collect()
        if serve and os.environ.get("BENCH_DISPATCH", "1") != "0" and not over_budget(
            0.848, "dispatch parity sweep", "dispatch_skipped"
        ):
            # Unified-dispatch pp×tp sweep: one engine over a pipeline ×
            # tensor mesh (pp_tp_serve_tok_per_s liveness floor) and the
            # GSPMD leader/follower step-program replayed against it
            # (dispatch_parity, exact-1.0 gate). Runs the tiny model — the
            # sweep boots THREE engines (reference, leader, follower), so
            # the headline checkpoint would not fit; this is the dispatch
            # plane's harness metric, not the 8B headline.
            try:
                dp = dispatch_parity_sweep(
                    os.environ.get("BENCH_DISPATCH_MODEL", "tiny-llm"),
                    mesh_spec=os.environ.get(
                        "BENCH_DISPATCH_MESH", "pp=2,tp=2"),
                )
                secondary.update(dp)  # marker key = [SKIP] + warn in gate
            except Exception as e:
                print(f"# dispatch parity sweep failed: {e!r}", flush=True)
                secondary["dispatch_sweep_error"] = 0.0
            gc.collect()
        if serve and os.environ.get("BENCH_ZOO", "1") != "0" and not over_budget(
            0.88, "model zoo sweep", "zoo_skipped"
        ):
            # Model-zoo + tenancy sweep (ISSUE 19): two tiny models through
            # one ModelZoo (hot=1) to price a steady-state swap-in, then a
            # two-tenant overload on the re-resident engine; perf_gate
            # floors tenant_isolation >= 0.5 and ceilings zoo_swap_in_s at
            # 60. Tiny models on purpose: the sweep boots three engine
            # incarnations and the headline checkpoint would not fit twice.
            try:
                zs = zoo_sweep(
                    os.environ.get("BENCH_ZOO_MODEL_A", "tiny-llm"),
                    os.environ.get("BENCH_ZOO_MODEL_B", "tiny-mla"),
                )
                secondary.update(zs)
            except Exception as e:
                print(f"# model zoo sweep failed: {e!r}", flush=True)
                secondary["zoo_sweep_error"] = 0.0
            gc.collect()
        real_dir = os.environ.get("BENCH_REAL_CKPT_DIR", "")
        if (
            real_dir
            and os.path.isfile(os.path.join(real_dir, "config.json"))
            and not over_budget(0.9, "real-checkpoint probe", "real_ckpt_skipped")
        ):
            try:
                secondary.update(real_ckpt_metrics(real_dir))
            except Exception as e:
                print(f"# real-checkpoint probe failed: {e!r}", flush=True)
                secondary["real_ckpt_error"] = 0.0
            gc.collect()
        if not serve and not raw_attempted:
            # serve disabled/failed and the raw sweep was never attempted:
            # it becomes the headline. (If it was attempted and FAILED, do
            # not re-run the identical sweep — fail loudly below instead.)
            raw_tps = run_raw()
        if not serve and not raw_tps:
            raise SystemExit("bench: both serve-path and raw sweeps failed")
        if serve:
            line = {
                "metric": f"serve_tok_per_s_{model}-int8-kv8_b{B}_{platform}",
                "value": round(serve["tok_per_s"], 1),
                "unit": "tok/s/chip",
                "vs_baseline": round(serve["tok_per_s"] / 2000.0, 3),
                "p50_ttft_ms": round(serve.get("p50_ttft_ms", -1.0), 1),
                "p95_ttft_ms": round(serve.get("p95_ttft_ms", -1.0), 1),
                # health evidence: the degenerate-window guard's inputs
                "window_errors": serve.get("window_errors", 0.0),
                "mean_completion_tokens": round(
                    serve.get("mean_completion_tokens", -1.0), 1
                ),
            }
            if "engine_direct_tok_per_s" in serve:
                # the serving-layer tax, measured in the SAME process/run —
                # and its ratio as a first-class gated metric
                # (scripts/perf_gate.py): serve ÷ engine-direct
                line["engine_direct_tok_per_s"] = round(
                    serve["engine_direct_tok_per_s"], 1
                )
                eff = serve_efficiency(serve)
                if eff is not None:
                    line["serve_efficiency"] = round(eff, 3)
            if "spec_accept_rate" in serve:
                # self-speculative decoding over the headline window (the
                # unique workload is the drafter's WORST case — the
                # repetitive sweep in secondary is its best case)
                line["spec_accept_rate"] = round(serve["spec_accept_rate"], 3)
                line["spec_tok_per_call"] = round(serve["spec_tok_per_call"], 2)
            if "prefill_tok_per_s" in serve:
                # prefill economy over the headline window, promoted where
                # scripts/perf_gate.py reads it: true prompt tok/s (floor),
                # pad-waste of the staging shape (ceiling), and the distinct
                # prefill executable count from the compile ledger — the
                # ragged packed path's whole case is these moving together
                line["prefill_tok_per_s"] = serve["prefill_tok_per_s"]
                line["prefill_pad_waste_pct"] = serve["prefill_pad_waste_pct"]
                line["prefill_executables"] = serve.get(
                    "prefill_executables", 0.0
                )
            if "oversub_kv_preempted" in secondary:
                # the oversubscription sweep's pool counters, promoted into
                # the line of record: preempt/restore churn, sheds, and the
                # admitted tail under 2x slot pressure
                line["oversub_preempted"] = secondary["oversub_kv_preempted"]
                line["oversub_restored"] = secondary["oversub_kv_restored"]
                line["oversub_shed"] = secondary["oversub_kv_shed"]
                line["oversub_p95_ttft_ms"] = secondary.get(
                    "oversub_p95_ttft_ms", -1.0
                )
                line["oversub_window_errors"] = secondary.get(
                    "oversub_window_errors", 0.0
                )
            if "paged_admit_ratio" in secondary:
                # the paged shared-prefix sweep's gated metrics, promoted
                # into the line of record where scripts/perf_gate.py reads
                # them (admit ratio floor 3.0, cow ceiling 2.0/req, leak
                # count exact-zero)
                line["paged_admit_ratio"] = secondary["paged_admit_ratio"]
                line["cow_copies_per_req"] = secondary.get(
                    "cow_copies_per_req", 0.0
                )
                line["paged_block_leaks"] = secondary.get(
                    "paged_block_leaks", 0.0
                )
                line["paged_tok_per_s"] = secondary.get("paged_tok_per_s", 0.0)
                if "paged_hbm_bytes_ratio" in secondary:
                    # physical-pool HBM savings (floor 2.5 in perf_gate):
                    # contiguous-equivalent ÷ physically-resident KV bytes
                    line["paged_hbm_bytes_ratio"] = secondary[
                        "paged_hbm_bytes_ratio"
                    ]
            if "migration_count" in secondary:
                # the 2-engine migration sweep's gated metrics, promoted
                # into the line of record where scripts/perf_gate.py reads
                # them (count floor 1, TTFT-gain floor 1.0)
                line["migration_count"] = secondary["migration_count"]
                line["migrated_kv_mb"] = secondary.get("migrated_kv_mb", 0.0)
                line["migrate_p95_ttft_ms"] = secondary.get(
                    "migrate_p95_ttft_ms", -1.0
                )
                line["migrate_off_p95_ttft_ms"] = secondary.get(
                    "migrate_off_p95_ttft_ms", -1.0
                )
                if "migrate_ttft_gain" in secondary:
                    line["migrate_ttft_gain"] = secondary["migrate_ttft_gain"]
            if "prefix_route_hit_rate" in secondary:
                # the prefix-locality routing sweep's gated metrics,
                # promoted into the line of record where
                # scripts/perf_gate.py reads them (hit-rate floor 0.5)
                line["prefix_route_hit_rate"] = secondary[
                    "prefix_route_hit_rate"
                ]
                line["prefix_fetch_count"] = secondary.get(
                    "prefix_fetch_count", 0.0
                )
                line["route_p95_ttft_ms"] = secondary.get(
                    "route_p95_ttft_ms", -1.0
                )
                line["route_off_p95_ttft_ms"] = secondary.get(
                    "route_off_p95_ttft_ms", -1.0
                )
                line["route_admitted_per_chip"] = secondary.get(
                    "route_admitted_per_chip", 0.0
                )
                if "route_ttft_gain" in secondary:
                    line["route_ttft_gain"] = secondary["route_ttft_gain"]
                if "prefix_fetch_speedup" in secondary:
                    line["prefix_fetch_speedup"] = secondary[
                        "prefix_fetch_speedup"
                    ]
            if "dispatch_parity" in secondary:
                # the pp×tp dispatch sweep's gated metrics, promoted into
                # the line of record where scripts/perf_gate.py reads them
                # (parity exact-1.0, serve liveness floor)
                line["dispatch_parity"] = secondary["dispatch_parity"]
                line["pp_tp_serve_tok_per_s"] = secondary.get(
                    "pp_tp_serve_tok_per_s", 0.0
                )
            for ek in (
                f"embed_per_s_nomic-embed-text_b1_{platform}",
                f"embed_per_s_qwen3-embedding-8b-int8_b64_d1024_{platform}",
                # raw-decode kernel evidence, promoted so the perf_gate
                # floors and the cross-round drift warning can see them: the
                # headline-shape B=112 sweep (the 6000 tok/s climb of
                # record) and the S=32k int8-latent MLA sweep (the blocked
                # s8 kernel's only on-hardware number)
                f"raw_decode_tok_per_s_{model}-int8_kv8_b112_{platform}",
                f"raw_decode_tok_per_s_mla-8b-int8_kv8_b4_s32768_{platform}",
                "layers_gbps",
                "attn_us_per_cell",
                "attn_us_per_cell_paged",
                # cold-start sweep (ISSUE 18), promoted so the perf_gate
                # ceilings can see them: boot→first-token with a warm
                # shipped cache (the <10 s acceptance bar), with an empty
                # cache (<60 s), time to fully-warm, background compile
                # count, and the peer warm-fill leg's first token
                "coldstart_first_token_s",
                "coldstart_first_token_cold_s",
                "coldstart_fully_warm_s",
                "warmup_bg_compiles",
                "coldstart_peer_first_token_s",
                # model-zoo + tenancy sweep (ISSUE 19), promoted so the
                # perf_gate floor/ceiling pair can see them: the steady-
                # state parked-tree swap-in wall and tenant B's
                # goodput_ratio while tenant A floods past its quota
                "zoo_swap_in_s",
                "tenant_isolation",
                "tenant_a_shed",
            ):
                if ek in secondary:
                    # promoted top-level under the exact perf_gate key names:
                    # nested under "secondary" the ABS_MIN embed floors can
                    # never fire (metric() only reads flat keys)
                    line[ek] = secondary[ek]
            if "recorder_dropped_events" in serve:
                # flight-recorder health over the headline window, promoted
                # where scripts/perf_gate.py reads it (exact-zero drops, like
                # paged_block_leaks) plus the measured overhead share
                line["recorder_dropped_events"] = serve[
                    "recorder_dropped_events"
                ]
                line["recorder_overhead_pct"] = serve.get(
                    "recorder_overhead_pct", 0.0
                )
            if "itl_p95_ms" in serve:
                # token pacing over the headline window (perf observatory),
                # promoted where scripts/perf_gate.py reads it: per-token
                # ITL p95 is the streaming-smoothness ceiling
                line["itl_p50_ms"] = serve["itl_p50_ms"]
                line["itl_p95_ms"] = serve["itl_p95_ms"]
            if "waterfall_decode_p95_ms" in serve:
                # latency waterfall over the headline window, promoted where
                # scripts/perf_gate.py reads it: the per-stage p95s of the
                # exact wall partition plus its coverage ratio (stages must
                # sum to the measured wall — the acceptance invariant)
                for wk in ("waterfall_admit_wait_p95_ms",
                           "waterfall_prefill_queue_p95_ms",
                           "waterfall_prefill_compute_p95_ms",
                           "waterfall_decode_p95_ms",
                           "waterfall_stall_p95_ms",
                           "waterfall_total_p95_ms",
                           "waterfall_coverage"):
                    if wk in serve:
                        line[wk] = serve[wk]
            if "goodput_tok_per_s" in serve:
                # SLO-conforming tokens/s (DistServe's metric) beside the
                # raw headline — the gap between them is the SLO-violating
                # share of the raw number
                line["goodput_tok_per_s"] = serve["goodput_tok_per_s"]
                line["goodput_ratio"] = serve.get("goodput_ratio", 1.0)
            if "decode_mbu" in serve:
                # live roofline from sampled decode rounds: the continuous
                # descendant of the one-off layers_gbps microbench
                line["decode_mbu"] = serve["decode_mbu"]
                line["decode_mfu"] = serve["decode_mfu"]
            if "phase_pct" in serve:
                # where the engine loop's wall-clock went during the window
                line["serve_phase_pct"] = serve["phase_pct"]
            if secondary:
                line["secondary"] = secondary
            print(json.dumps(line))
            return
        # serve path unavailable: the raw measurement (already computed
        # above) becomes the headline — never run the same sweep twice
        B, kv, tps = 112, "_kv8", raw_tps

    line = {
        "metric": f"decode_tok_per_s_{model}-int8{kv}_b{B}_{platform}",
        "value": round(tps, 1),
        "unit": "tok/s/chip",
        "vs_baseline": round(tps / 2000.0, 3),
    }
    if secondary:
        line["secondary"] = secondary
    print(json.dumps(line))


def load_trace_source(src: str) -> tuple[list[dict], int]:
    """(records, rejected) from a capture path or a `synth:<kind>:<n>[:seed]`
    spec (kinds: chat / embed / longctx / agent — telemetry/workload.py)."""
    from llm_mcp_tpu.telemetry import workload

    if src.startswith("synth:"):
        parts = src.split(":")
        kind = parts[1] if len(parts) > 1 and parts[1] else "chat"
        n = int(parts[2]) if len(parts) > 2 and parts[2] else 32
        seed = int(parts[3]) if len(parts) > 3 and parts[3] else 0
        return workload.synth_trace(kind, n, seed=seed), 0
    return workload.load_trace(src)


def build_replay_stream(
    records: list[dict], *, seed: int = 0, compress: float = 1.0
) -> tuple[list[tuple[float, dict, object]], str]:
    """The deterministic issue plan: [(gap_s, record, prompt)] plus its
    sha256 digest. `prompt` is the record's raw token ids when captured
    with TPU_WORKLOAD_IDS=1 (token-identical replay), else deterministic
    text derived from the prefix-chain head hash (prefix-sharing structure
    survives). Same records + seed + compress -> byte-identical plan —
    the digest is the proof perf_gate's replay_determinism check rides."""
    import hashlib
    import random

    from llm_mcp_tpu.telemetry import workload

    rng = random.Random(seed)
    plan: list[tuple[float, dict, object]] = []
    h = hashlib.sha256(f"seed={seed} compress={compress}".encode())
    prev_ts: float | None = None
    for rec in records:
        ts = float(rec["ts"])
        trace_gap = 0.0 if prev_ts is None else max(0.0, ts - prev_ts)
        prev_ts = ts
        gap = next_arrival_gap(rng, trace_gap=trace_gap, compress=compress)
        prompt: object = (
            list(rec["ids"]) if rec.get("ids")
            else workload.prompt_text_for(rec)
        )
        plan.append((gap, rec, prompt))
        h.update(json.dumps(
            [round(gap, 9), prompt, rec.get("mt", 0), rec.get("temp", 0.0),
             rec.get("top_k", 0), rec.get("top_p", 1.0)],
            separators=(",", ":"),
        ).encode())
    return plan, h.hexdigest()


def trace_replay_metrics(
    trace_src: str,
    *,
    model: str = "tiny-llm",
    max_slots: int = 4,
    max_seq_len: int = 512,
    decode_chunk: int = 4,
    quant: str = "",
    kv_quant: str = "",
    compress: float | None = None,
    seed: int | None = None,
    max_tokens_cap: int = 0,
    collect_outputs: bool = False,
) -> dict:
    """Open-loop deterministic replay of a captured (or synthesized)
    workload trace against a fresh engine — the BENCH_TRACE mode.

    Issues the trace's requests with faithful inter-arrival gaps divided
    by the time-compression factor (BENCH_TRACE_COMPRESS), seeded by
    BENCH_TRACE_SEED so two runs issue byte-identical request streams
    (replay_determinism proves it by building the plan twice and comparing
    digests). Records captured with raw ids replay token-identically;
    hash-only records replay as deterministic text derived from their
    prefix-chain head hashes. Returns replay_* metrics plus the engine's
    latency-waterfall p95s over the replayed window.

    BENCH_CONSTRAIN=1 arms grammar-constrained decoding for records that
    carry a `schema` field (the synth:agent kind stamps one per tool-call
    burst): each such request replays under a json_schema constraint, and
    the run promotes constrain_mask_us_per_tok / schema_valid_rate /
    constrain_spec_accept_rate into the line of record. The agent schemas
    are closed (every field enum/boolean), so the accepting state has no
    outgoing transitions and the mask forces EOS — schema_valid_rate is
    exactly 1.0 on any model, which is what perf_gate demands."""
    import hashlib
    import threading

    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.executor.engine import GenRequest

    if compress is None:
        compress = float(os.environ.get("BENCH_TRACE_COMPRESS", "1") or 1.0)
    if seed is None:
        seed = int(os.environ.get("BENCH_TRACE_SEED", "0") or 0)
    records, rejected = load_trace_source(trace_src)
    out: dict = {
        "replay_requests": float(len(records)),
        "replay_rejected_lines": float(rejected),
        "replay_compress": float(compress),
    }
    if not records:
        out["replay_determinism"] = 0.0
        return out
    plan, sha_a = build_replay_stream(records, seed=seed, compress=compress)
    _, sha_b = build_replay_stream(records, seed=seed, compress=compress)
    out["replay_determinism"] = 1.0 if sha_a == sha_b else 0.0
    out["replay_stream_sha"] = sha_a[:16]

    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    eng = GenerationEngine(
        model, max_slots=max_slots, max_seq_len=max_seq_len, dtype=dtype,
        decode_chunk=decode_chunk, quant=quant, kv_quant=kv_quant,
    ).start()
    results: dict[str, str] = {}
    errors = [0]
    lock = threading.Lock()
    consumers: list[threading.Thread] = []

    def consume(rid: str, req: GenRequest) -> None:
        parts: list[str] = []
        while True:
            evt = req.out.get()
            if not isinstance(evt, dict):
                break
            if evt.get("type") == "token":
                parts.append(evt["text"])
            elif evt.get("type") == "done":
                break
            elif evt.get("type") == "error":
                with lock:
                    errors[0] += 1
                break
        with lock:
            results[rid] = "".join(parts)

    constrain = os.environ.get("BENCH_CONSTRAIN", "") == "1"
    try:
        t0 = time.perf_counter()
        for gap, rec, prompt in plan:
            if gap > 0:
                time.sleep(gap)
            ids = (
                prompt if isinstance(prompt, list)
                else [int(t) for t in eng.tokenizer.encode(prompt)]
            )
            mt = int(rec.get("mt", 16)) or 1
            if max_tokens_cap:
                mt = min(mt, max_tokens_cap)
            constraint = (
                {"type": "json_schema", "schema": rec["schema"]}
                if constrain and rec.get("schema") else None
            )
            if constraint is not None:
                # a closed agent schema forces ~30-60 byte tokens before
                # its EOS-only accepting state; the CPU smoke cap (16)
                # would cut every request off at finish="length" and
                # schema_valid_rate could never reach its exact-1.0 gate
                mt = max(mt, 64)
                # and the completion needs real sequence headroom: agent
                # prompts run to the context edge, and a constrained
                # request retired at the row budget finishes "length" in
                # a non-accepting state — keep the prompt TAIL (recency
                # matters for agent turns) and reserve room for the call
                if len(ids) > max_seq_len - 96:
                    ids = ids[-(max_seq_len - 96):]
            req = GenRequest(
                prompt_ids=ids, max_tokens=mt,
                temperature=float(rec.get("temp", 0.0)),
                top_k=int(rec.get("top_k", 0)),
                top_p=float(rec.get("top_p", 1.0)),
                constraint=constraint,
            )
            rid = str(rec.get("rid") or req.request_id)
            eng.submit(req)
            th = threading.Thread(target=consume, args=(rid, req), daemon=True)
            th.start()
            consumers.append(th)
        # drain: open-loop issuance is done; wait for the tail to finish
        deadline = time.time() + 120.0
        for th in consumers:
            th.join(timeout=max(0.1, deadline - time.time()))
        wall = time.perf_counter() - t0
        out["replay_finished"] = float(eng.finished_requests)
        out["replay_admitted"] = float(eng.total_requests)
        out["replay_window_errors"] = float(errors[0] + eng.total_errors)
        out["replay_tok_per_s"] = round(eng.finished_tokens / wall, 1) if wall > 0 else 0.0
        out["replay_wall_s"] = round(wall, 3)
        ws = eng.waterfall_stats()
        out["waterfall_coverage"] = ws.get("coverage", 1.0)
        for stage in ("admit_wait", "prefill_queue", "prefill_compute",
                      "decode", "stall"):
            out[f"waterfall_{stage}_p95_ms"] = (
                (ws.get("stages") or {}).get(stage, {}).get("p95_ms", 0.0)
            )
        out["waterfall_total_p95_ms"] = ws.get("total_p95_ms", 0.0)
        # constrained-decoding line of record: only when the replay actually
        # carried constrained traffic — unconstrained runs keep these keys
        # absent so perf_gate reports [SKIP], never a vacuous 1.0 pass
        cs = getattr(eng, "constrain_stats", None)
        cs = cs() if cs is not None else {}
        if cs.get("requests", 0.0) > 0:
            out["constrain_requests"] = cs["requests"]
            out["constrain_mask_us_per_tok"] = round(cs["mask_us_per_tok"], 2)
            out["schema_valid_rate"] = cs["schema_valid_rate"]
            if cs.get("spec_drafted", 0.0) > 0:
                out["constrain_spec_accept_rate"] = round(
                    cs["spec_accept_rate"], 4
                )
        h = hashlib.sha256()
        for rid in sorted(results):
            h.update(f"{rid}\x00{results[rid]}\x01".encode())
        out["replay_output_sha"] = h.hexdigest()[:16]
        if collect_outputs:
            out["outputs"] = dict(results)
    finally:
        eng.shutdown()
    return out


def capture_replay_smoke(
    model: str = "tiny-llm", n_requests: int = 5, max_tokens: int = 8
) -> dict:
    """CPU-smoke capture→replay round trip: serve a few greedy requests
    with workload capture armed (raw ids embedded), dump the ring to a
    trace file, replay it through a FRESH engine, and compare — the
    replayed stream must reproduce the captured admitted-request count
    and token-identical outputs (replay_match carries both)."""
    import tempfile

    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.telemetry import workload

    prior = workload.get_workload()
    cap = workload.WorkloadTrace(include_ids=True, trace_path="")
    workload.set_workload(cap)
    outputs: dict[str, str] = {}
    try:
        eng = GenerationEngine(
            model, max_slots=2, max_seq_len=512, dtype=jnp.float32,
            decode_chunk=4,
        ).start()
        try:
            for i in range(n_requests):
                out = eng.generate(
                    f"capture request {i}: one plain line about replay.",
                    max_tokens=max_tokens, temperature=0.0,
                )
                # the finished request's record is in the ring before its
                # done event publishes — newest entry is this request
                rec = cap.snapshot(1)[0]
                outputs[rec["rid"]] = out["text"]
            captured = eng.finished_requests
        finally:
            eng.shutdown()
    finally:
        workload.set_workload(prior)
    fd, path = tempfile.mkstemp(suffix=".jsonl", prefix="llmtpu-trace-")
    os.close(fd)
    try:
        cap.dump(path)
        rp = trace_replay_metrics(
            path, model=model, max_slots=2, max_seq_len=512, decode_chunk=4,
            compress=1000.0, collect_outputs=True,
        )
    finally:
        os.unlink(path)
    replay_out = rp.pop("outputs", {})
    rp["replay_captured"] = float(captured)
    rp["replay_match"] = (
        1.0
        if replay_out == outputs and rp.get("replay_finished") == float(captured)
        else 0.0
    )
    return rp


def migration_sweep(
    model: str, *, n_clients: int = 8, rounds: int = 2, max_tokens: int = 32,
    max_slots: int = 2, max_seq_len: int = 512, decode_chunk: int = 4,
    quant: str = "", kv_quant: str = "", target_ttft_ms: float = 250.0,
) -> dict[str, float]:
    """2-engine oversubscribed migration sweep: every client hits engine A
    (slots << clients, KV pool armed) while an identical engine B sits idle
    beside it. The ON leg runs a MigrationCoordinator on a tight interval,
    so queued-behind-a-long-tail requests get re-homed to B and offloaded
    snapshots drain to it; the OFF leg applies the same pressure with
    queueing/shedding only. Reports both admitted p95 TTFTs plus the
    migration counters — `migration_count` and `migrate_ttft_gain`
    (OFF p95 ÷ ON p95) carry scripts/perf_gate.py floors.

    Clients replicate the serve path's admission gate (api/inference.py):
    poll `admission_state()` and honor the Retry-After backoff before
    submitting, so TTFT includes the shed penalty exactly as an HTTP
    client would pay it. That is where migration wins: the coordinator
    drains A's queue/offloads into B, A's offered load falls back under
    the watermark, and the gate reopens — avoided backoff sleep, which
    holds even when both engines share one accelerator's silicon.

    Drives the engines directly (generate_stream), not the HTTP serve
    path: the coordinator re-homes each request's consumer queue across
    engines in-process, which is exactly the drain path api/server.py
    wires up — and two model replicas behind one CoreServer would measure
    the router, not the migration."""
    import threading

    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.executor.migration import MigrationCoordinator
    from llm_mcp_tpu.parallel import make_mesh

    devices = jax.devices()
    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    if len(devices) < 2:
        # one accelerator = zero-sum silicon: the second engine's rounds
        # would interleave with the first's on the same device and the
        # TTFT comparison measures contention, not migration. Emit a
        # marker instead of the gated keys — perf_gate [SKIP]s them with
        # a warning, per the single-engine escape hatch.
        print("# migration sweep needs >= 2 devices; skipping", flush=True)
        return {"migrate_single_device": 0.0}
    if platform == "cpu":
        try:
            cores = len(os.sched_getaffinity(0))
        except AttributeError:
            cores = os.cpu_count() or 1
        if cores < 2:
            # XLA host "devices" share one core pool: on a single core the
            # second engine's decode serializes with the first's, so the
            # ON leg measures contention + coordinator overhead, never
            # added capacity. Same escape hatch as the single-device case.
            print(
                "# migration sweep needs >= 2 cores for additive capacity;"
                " skipping", flush=True,
            )
            return {"migrate_single_device": 0.0}
    meshes = [make_mesh("", [devices[0]]), make_mesh("", [devices[1]])]

    def leg(migrate: bool) -> dict[str, float]:
        # engines read TPU_MIGRATE / TPU_KV_HOST_OFFLOAD at construction;
        # restore whatever the operator had set once both replicas exist
        prior = {k: os.environ.get(k)
                 for k in ("TPU_MIGRATE", "TPU_KV_HOST_OFFLOAD")}
        os.environ["TPU_KV_HOST_OFFLOAD"] = "1"
        if migrate:
            os.environ["TPU_MIGRATE"] = "1"
        else:
            os.environ.pop("TPU_MIGRATE", None)
        try:
            def mk(mesh) -> "GenerationEngine":
                # each replica on its OWN 1-device mesh: B's capacity must
                # be additive, not interleaved with A's on one device
                # tight TTFT target on both replicas: the token-budget
                # scheduler's deadline pacing otherwise EQUALIZES both
                # legs — it delays admission toward the (default 2 s)
                # deadline whenever there is slack, absorbing exactly the
                # headroom migration frees. With pacing off the critical
                # path, the comparison measures queueing + shed backoff.
                return GenerationEngine(
                    model, mesh=mesh, max_slots=max_slots,
                    max_seq_len=max_seq_len, dtype=dtype,
                    decode_chunk=decode_chunk, quant=quant,
                    kv_quant=kv_quant, target_ttft_ms=target_ttft_ms,
                ).start()

            a, b = mk(meshes[0]), mk(meshes[1])
        finally:
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        coord = None
        lock = threading.Lock()
        ttfts: list[float] = []
        errors = [0]
        try:
            # warm BOTH engines with the measured workload's shapes —
            # prefill bucket AND decode batches 1..max_slots. B only ever
            # sees traffic via migration, so without this its first
            # compiles land inside the window and get charged to the ON
            # leg's TTFTs.
            def _warm_one(eng: "GenerationEngine", i: int) -> None:
                eng.generate(
                    f"migration sweep warmup {i}: write one plain line"
                    " about queueing.",
                    max_tokens=max_tokens, temperature=0.0,
                )

            for eng in (a, b):
                ws = [
                    threading.Thread(
                        target=_warm_one, args=(eng, i), daemon=True
                    )
                    for i in range(max_slots)
                ]
                for t in ws:
                    t.start()
                for t in ws:
                    t.join(timeout=300.0)
            if migrate:
                coord = MigrationCoordinator(
                    {"bench-src": a, "bench-dst": b}, burst=4,
                    interval_s=0.05,
                ).start()

            def client(cid: int) -> None:
                for r in range(rounds):
                    t0 = time.perf_counter()
                    # the serve path's load-shedding gate (api/inference.py
                    # 429 + Retry-After), honored like the HTTP clients do —
                    # capped so one pessimistic drain estimate can't eat the
                    # whole window. The shed sleep is INSIDE the TTFT.
                    while True:
                        shed, retry = a.admission_state()
                        if not shed:
                            break
                        a.note_shed()
                        if coord is not None:
                            coord.note_pressure()
                        time.sleep(min(2.0, max(0.25, retry)))
                    got = False
                    for evt in a.generate_stream(
                        f"migration sweep client {cid} round {r}: write"
                        " one plain line about queueing.",
                        max_tokens=max_tokens, temperature=0.0,
                    ):
                        if evt["type"] == "token" and not got:
                            got = True
                            with lock:
                                ttfts.append(
                                    (time.perf_counter() - t0) * 1000.0
                                )
                        elif evt["type"] == "error":
                            with lock:
                                errors[0] += 1
                        elif evt["type"] == "done":
                            break

            threads = [
                threading.Thread(target=client, args=(i,), daemon=True)
                for i in range(n_clients)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600.0)
            out = {
                "p95_ttft_ms": (
                    sorted(ttfts)[max(0, int(len(ttfts) * 0.95) - 1)]
                    if ttfts else -1.0
                ),
                "requests": float(len(ttfts)),
                "errors": float(errors[0]),
            }
            if coord is not None:
                cst = coord.stats()
                out["migration_count"] = (
                    cst["snapshots_moved_total"] + cst["requeues_total"]
                )
                out["migrated_kv_mb"] = cst["bytes_total"] / (1 << 20)
                out["migrate_failed"] = cst["failed_total"]
                out["migrated_in"] = b.migration_stats().get(
                    "migrated_in_total", 0.0
                )
            return out
        finally:
            if coord is not None:
                coord.stop()
            a.shutdown()
            b.shutdown()
            gc.collect()

    on = leg(True)
    off = leg(False)
    res = {
        "migrate_p95_ttft_ms": round(on["p95_ttft_ms"], 1),
        "migrate_off_p95_ttft_ms": round(off["p95_ttft_ms"], 1),
        "migration_count": on.get("migration_count", 0.0),
        "migrated_kv_mb": round(on.get("migrated_kv_mb", 0.0), 3),
        "migrate_window_errors": on["errors"] + off["errors"],
        "migrate_requests": on["requests"],
    }
    if on.get("migrate_failed", 0.0):
        res["migrate_failed"] = on["migrate_failed"]
    if on["p95_ttft_ms"] > 0 and off["p95_ttft_ms"] > 0:
        res["migrate_ttft_gain"] = round(
            off["p95_ttft_ms"] / on["p95_ttft_ms"], 3
        )
    return res


def prefix_routing_sweep(
    model: str, *, n_clients: int = 8, rounds: int = 3, max_tokens: int = 16,
    max_slots: int = 4, max_seq_len: int = 512, decode_chunk: int = 4,
    quant: str = "", kv_quant: str = "", target_ttft_ms: float = 250.0,
    shared_tokens: int = 96, shared_frac: float = 0.9, fetch_min: int = 0,
    poisson_rps: float = 0.0,
) -> dict[str, float]:
    """2-engine prefix-locality routing sweep: 90% of clients share one
    long prompt prefix that only engine A holds resident (primed before
    the window); a real Router over an in-memory catalog makes every
    placement decision from the engines' own advertised tags (prefix
    digest, queue depth, tags_at), refreshed on a discovery-style loop.
    The ON leg routes with TPU_PREFIX_ROUTE=1 — the holder wins shared
    requests within its headroom band and spill-overs pull the prefix via
    the in-process fetch path (prefix_export → prefix_import, the same
    data path the PrefixFetch RPC serves) — the OFF leg is today's
    benchmark-ranked routing, byte-for-byte. `prefix_route_hit_rate`
    ((local + fetch) ÷ routed requests) carries the scripts/perf_gate.py
    floor; p95 TTFT and admitted-per-chip of both legs ride the record.

    The OFF leg also measures the fetch-vs-recompute crossover on fresh
    engines: wall time for B to prefill the shared prefix from scratch vs
    exporting it from A and importing pin-only — the measurement behind
    the TPU_PREFIX_FETCH_MIN_TOKENS=256 default (fetch must win above it).

    `poisson_rps` > 0 switches the closed-loop clients to open-loop
    Poisson arrivals (exponential interarrival per client, aggregate rate
    `poisson_rps`) — bursty arrivals are where locality routing's queue
    penalty term earns its keep (BENCH_POISSON_RPS)."""
    import random
    import threading

    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.parallel import make_mesh
    from llm_mcp_tpu.routing import Router
    from llm_mcp_tpu.routing import prefix as prefix_fp
    from llm_mcp_tpu.state import Catalog, Database

    devices = jax.devices()
    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    if len(devices) < 2:
        # same escape hatch as migration_sweep: on one accelerator the
        # second engine's rounds interleave with the first's and the leg
        # comparison measures contention, not locality. Marker key →
        # perf_gate [SKIP]s the gated metrics with a warning.
        print("# prefix routing sweep needs >= 2 devices; skipping",
              flush=True)
        return {"prefix_route_single_device": 0.0}
    if platform == "cpu":
        try:
            cores = len(os.sched_getaffinity(0))
        except AttributeError:
            cores = os.cpu_count() or 1
        if cores < 2:
            print("# prefix routing sweep needs >= 2 cores for additive"
                  " capacity; skipping", flush=True)
            return {"prefix_route_single_device": 0.0}
    meshes = [make_mesh("", [devices[0]]), make_mesh("", [devices[1]])]

    def leg(route_on: bool) -> dict[str, float]:
        # the router reads TPU_PREFIX_ROUTE / TPU_PREFIX_FETCH_MIN_TOKENS
        # at decision time, so the env must hold for the whole window
        prior = {k: os.environ.get(k)
                 for k in ("TPU_PREFIX_ROUTE", "TPU_PREFIX_FETCH_MIN_TOKENS")}
        os.environ["TPU_PREFIX_ROUTE"] = "1" if route_on else "0"
        if fetch_min > 0:
            os.environ["TPU_PREFIX_FETCH_MIN_TOKENS"] = str(fetch_min)
        try:
            def mk(mesh) -> "GenerationEngine":
                return GenerationEngine(
                    model, mesh=mesh, max_slots=max_slots,
                    max_seq_len=max_seq_len, dtype=dtype,
                    decode_chunk=decode_chunk, quant=quant,
                    kv_quant=kv_quant, target_ttft_ms=target_ttft_ms,
                    prompt_cache_mb=64,
                ).start()

            a, b = mk(meshes[0]), mk(meshes[1])
            engines = {"bench-a": a, "bench-b": b}
            db = Database(":memory:")
            catalog = Catalog(db)
            catalog.upsert_model(model, params_b=1.0, kind="llm")
            for i, dev_id in enumerate(engines):
                catalog.upsert_device(dev_id, addr=f"127.0.0.1:{8081 + i}",
                                      tags={"kv_headroom": 0.8})
                catalog.sync_device_models(dev_id, [model])
            # B carries the better benchmark: baseline routing sends ALL
            # traffic to it, so the ON leg's holder-wins re-rank (A primed
            # with the shared prefix) is what the comparison isolates
            catalog.record_benchmark("bench-a", model, "generate", tps=900,
                                     latency_ms=40)
            catalog.record_benchmark("bench-b", model, "generate", tps=2400,
                                     latency_ms=40)
            router = Router(db, has_openrouter=False, has_openai=False)

            def refresh_tags() -> None:
                # what register_local_device advertises, from the engines'
                # own state: digest + queue depth + freshness stamp
                for i, (dev_id, eng) in enumerate(engines.items()):
                    tags: dict = {
                        "kv_headroom": 0.8,
                        "queue_depth": float(eng.queue_depth()),
                        "tags_at": time.time(),
                    }
                    dg = eng.prefix_digest()
                    if dg:
                        tags["prefix_digest"] = dg
                    catalog.upsert_device(
                        dev_id, addr=f"127.0.0.1:{8081 + i}", tags=tags
                    )

            lock = threading.Lock()
            ttfts: list[float] = []
            counts = {"errors": 0.0, "local": 0.0, "fetch": 0.0,
                      "miss": 0.0, "fetch_ms": 0.0}
            out: dict[str, float] = {}
            try:
                # shared prefix: repeat a base phrase past `shared_tokens`
                base = ("you are a terse routing assistant for a TPU"
                        " serving fleet. answer in one short line. ")
                shared_text = base
                while len(a.tokenizer.encode(shared_text)) < shared_tokens:
                    shared_text += base

                # warm BOTH engines at the workload's prompt lengths (short
                # unique + long shared-length) so no prefill-bucket compile
                # lands inside either leg's window
                def _warm_one(eng: "GenerationEngine", i: int) -> None:
                    filler = (f"warmup filler {i}: note on queueing. "
                              * (shared_tokens // 4))
                    eng.generate(filler, max_tokens=max_tokens,
                                 temperature=0.0)
                    eng.generate(f"short warmup {i}.", max_tokens=4,
                                 temperature=0.0)

                for eng in engines.values():
                    ws = [
                        threading.Thread(target=_warm_one, args=(eng, i),
                                         daemon=True)
                        for i in range(max_slots)
                    ]
                    for t in ws:
                        t.start()
                    for t in ws:
                        t.join(timeout=300.0)
                # prime the holder: chains store on their second sighting
                for i in range(3):
                    a.generate(shared_text + f"prime {i}", max_tokens=2,
                               temperature=0.0)
                if route_on and not a.prefix_chains():
                    print("# prefix routing sweep: holder never stored a"
                          " chain; window will read as misses", flush=True)

                if not route_on:
                    # fetch-vs-recompute crossover, on engines that have
                    # never seen the shared prefix imported: B prefills it
                    # from scratch (1-token generate ≈ pure prefill), then
                    # pulls the same chain over the export/import path
                    probe = shared_text + "crossover probe"
                    pids = [int(t) for t in a.tokenizer.encode(probe)]
                    t0 = time.perf_counter()
                    b.generate(probe, max_tokens=1, temperature=0.0)
                    out["recompute_ms"] = (time.perf_counter() - t0) * 1e3
                    t0 = time.perf_counter()
                    payload = a.prefix_export(pids)
                    if payload is not None and b.prefix_import(payload):
                        out["fetch_ms"] = (time.perf_counter() - t0) * 1e3

                refresh_tags()
                stop_evt = threading.Event()

                def refresher() -> None:
                    # discovery-style tag refresh, fast enough that queue
                    # depth and newly imported digests steer mid-window
                    while not stop_evt.wait(0.25):
                        refresh_tags()

                rt = threading.Thread(target=refresher, daemon=True)
                rt.start()

                def client(cid: int) -> None:
                    rng = random.Random(0xC0FFEE + cid)
                    for r in range(rounds):
                        gap = next_arrival_gap(
                            rng, poisson_rps=poisson_rps,
                            n_clients=n_clients,
                        )
                        if gap > 0:
                            time.sleep(gap)
                        if rng.random() < shared_frac:
                            prompt = (shared_text + f"client {cid} round"
                                      f" {r}: one line on routing.")
                        else:
                            prompt = (f"unique client {cid} round {r}:"
                                      " write one plain line about"
                                      " schedulers.")
                        ids = [int(t) for t in a.tokenizer.encode(prompt)]
                        t0 = time.perf_counter()
                        dev = router.select_device(
                            model, "generate", prefix_ids=ids
                        )
                        dev_id = dev["id"] if dev else "bench-a"
                        eng = engines[dev_id]
                        if route_on:
                            # the serve path's fetch orchestration
                            # (api/server.py maybe_prefix_fetch), in-process
                            local = eng.prefix_match_len(ids)
                            if local > 0:
                                with lock:
                                    counts["local"] += 1
                            else:
                                got = router.best_prefix_peer(
                                    model, ids, exclude_device=dev_id,
                                    min_tokens=max(
                                        prefix_fp.fetch_min_tokens(),
                                        local + 1,
                                    ),
                                )
                                done = False
                                if got is not None:
                                    tf = time.perf_counter()
                                    payload = engines[
                                        got[0]["id"]
                                    ].prefix_export(ids)
                                    if payload is not None and \
                                            eng.prefix_import(payload):
                                        with lock:
                                            counts["fetch"] += 1
                                            counts["fetch_ms"] += (
                                                time.perf_counter() - tf
                                            ) * 1e3
                                        done = True
                                if not done:
                                    with lock:
                                        counts["miss"] += 1
                        # the serve path's admission gate, shed sleep
                        # INSIDE the TTFT (as an HTTP client pays it)
                        while True:
                            shed, retry = eng.admission_state()
                            if not shed:
                                break
                            eng.note_shed()
                            time.sleep(min(2.0, max(0.25, retry)))
                        got_tok = False
                        for evt in eng.generate_stream(
                            prompt, max_tokens=max_tokens, temperature=0.0
                        ):
                            if evt["type"] == "token" and not got_tok:
                                got_tok = True
                                with lock:
                                    ttfts.append(
                                        (time.perf_counter() - t0) * 1e3
                                    )
                            elif evt["type"] == "error":
                                with lock:
                                    counts["errors"] += 1
                            elif evt["type"] == "done":
                                break

                threads = [
                    threading.Thread(target=client, args=(i,), daemon=True)
                    for i in range(n_clients)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=600.0)
                stop_evt.set()
                rt.join(timeout=5.0)
                out.update({
                    "p95_ttft_ms": (
                        sorted(ttfts)[max(0, int(len(ttfts) * 0.95) - 1)]
                        if ttfts else -1.0
                    ),
                    "requests": float(len(ttfts)),
                    "errors": counts["errors"],
                    "local": counts["local"],
                    "fetch": counts["fetch"],
                    "miss": counts["miss"],
                    "fetch_window_ms": counts["fetch_ms"],
                })
                return out
            finally:
                a.shutdown()
                b.shutdown()
                db.close()
                gc.collect()
        finally:
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    on = leg(True)
    off = leg(False)
    decided = on["local"] + on["fetch"] + on["miss"]
    res = {
        "prefix_route_hit_rate": round(
            (on["local"] + on["fetch"]) / decided, 3
        ) if decided else 0.0,
        "prefix_fetch_count": on["fetch"],
        "route_p95_ttft_ms": round(on["p95_ttft_ms"], 1),
        "route_off_p95_ttft_ms": round(off["p95_ttft_ms"], 1),
        "route_admitted_per_chip": round(on["requests"] / 2.0, 1),
        "route_off_admitted_per_chip": round(off["requests"] / 2.0, 1),
        "route_requests": on["requests"],
        "route_window_errors": on["errors"] + off["errors"],
    }
    if on["p95_ttft_ms"] > 0 and off["p95_ttft_ms"] > 0:
        res["route_ttft_gain"] = round(
            off["p95_ttft_ms"] / on["p95_ttft_ms"], 3
        )
    if off.get("recompute_ms") and off.get("fetch_ms"):
        res["prefix_recompute_ms"] = round(off["recompute_ms"], 1)
        res["prefix_fetch_ms"] = round(off["fetch_ms"], 1)
        # > 1.0 = pulling the chain beats recomputing it at this length —
        # the evidence behind the TPU_PREFIX_FETCH_MIN_TOKENS default
        res["prefix_fetch_speedup"] = round(
            off["recompute_ms"] / off["fetch_ms"], 2
        )
    return res


def dispatch_parity_sweep(
    model: str = "tiny-llm", *, n_requests: int = 6, max_tokens: int = 16,
    max_slots: int = 2, max_seq_len: int = 256, decode_chunk: int = 4,
    prefill_chunk: int = 32, mesh_spec: str = "pp=2,tp=2",
) -> dict[str, float]:
    """Unified-dispatch pp×tp sweep (two perf_gate-floored keys):

    - `pp_tp_serve_tok_per_s`: greedy serve throughput of ONE engine booted
      over a pipeline×tensor mesh (layer axis on pp, heads on tp, GPipe
      stage-scan prefill) — the capacity-unlock configuration's liveness
      number.
    - `dispatch_parity`: the SAME traffic re-served through a GSPMD leader
      broadcasting its step-program over a real TCP command channel to an
      in-process follower engine. 1.0 iff every completion is
      token-identical to the local-arrays engine AND the follower's device
      arrays finish bit-identical to the leader's; anything else is 0.0 and
      fails the gate.

    Hosts without enough devices for the mesh emit the
    `dispatch_single_device` marker instead and perf_gate [SKIP]s the keys
    with a warning, like the 2-engine migration/routing sweeps."""
    import socket
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.executor.dispatch import GSPMDBackend
    from llm_mcp_tpu.models.configs import MODEL_CONFIGS
    from llm_mcp_tpu.models.llama import init_llama_params
    from llm_mcp_tpu.parallel.mesh import make_mesh
    from llm_mcp_tpu.parallel.sharding import llama_param_specs, shard_pytree

    need = 1
    for part in mesh_spec.split(","):
        _, _, v = part.partition("=")
        if v.strip():
            need *= int(v)
    devices = jax.devices()
    if len(devices) < need:
        print(f"# dispatch parity sweep needs >= {need} devices; skipping",
              flush=True)
        return {"dispatch_single_device": 0.0}
    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    mesh = make_mesh(mesh_spec, devices=devices[:need])
    cfg = MODEL_CONFIGS[model]
    # ONE param tree for every engine in the sweep (what a shared checkpoint
    # gives a real boot): a jitted born-sharded init differs from an eager
    # one by an ULP, which a random toy model amplifies into different
    # argmax tokens — that would measure compiler numerics, not dispatch.
    params = shard_pytree(
        init_llama_params(cfg, jax.random.PRNGKey(0), dtype=dtype),
        llama_param_specs(cfg), mesh)
    kw = dict(mesh=mesh, params=params, max_slots=max_slots,
              max_seq_len=max_seq_len, dtype=dtype, decode_chunk=decode_chunk,
              prefill_chunk=prefill_chunk, seed=0)
    shared = "shared dispatch preamble: alpha beta gamma delta epsilon. "
    prompts = [
        (shared + f"question {i}: name item {i} of the list")
        if i % 2 else f"short probe {i}"
        for i in range(n_requests)
    ]

    def serve(eng: "GenerationEngine") -> list[str]:
        texts: list[str | None] = [None] * len(prompts)

        def one(i: int) -> None:
            texts[i] = eng.generate(
                prompts[i], max_tokens=max_tokens, temperature=0.0)["text"]

        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(prompts))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return texts  # type: ignore[return-value]

    out: dict[str, float] = {}
    ref = GenerationEngine(model, **kw).start()
    try:
        ref.generate(prompts[0], max_tokens=2, temperature=0.0)  # compile
        tok0, t0 = ref.total_tokens, time.monotonic()
        want = serve(ref)
        wall = max(time.monotonic() - t0, 1e-9)
        out["pp_tp_serve_tok_per_s"] = round(
            (ref.total_tokens - tok0) / wall, 1)
    finally:
        ref.shutdown()
    gc.collect()

    with socket.socket() as s:  # free port for the command channel
        s.bind(("127.0.0.1", 0))
        addr = f"127.0.0.1:{s.getsockname()[1]}"
    lead_backend = GSPMDBackend(addr, connect_timeout_s=120.0)
    lead_backend._n_followers = 1  # the follower lives in this process
    follower = GenerationEngine(
        model, backend=GSPMDBackend(addr, connect_timeout_s=120.0), **kw)
    fol_thread = threading.Thread(target=follower.run_follower, daemon=True)
    fol_thread.start()
    leader = GenerationEngine(model, backend=lead_backend, **kw).start()
    try:
        got = serve(leader)
    finally:
        leader.shutdown()  # stop frame releases the follower loop
        fol_thread.join(timeout=120)
    state_ok = (
        not fol_thread.is_alive()
        and not leader.dead
        and np.array_equal(np.asarray(leader._ck), np.asarray(follower._ck))
        and np.array_equal(np.asarray(leader._cv), np.asarray(follower._cv))
    )
    out["dispatch_parity"] = 1.0 if (got == want and state_ok) else 0.0
    return out


def zoo_sweep(
    model_a: str = "tiny-llm", model_b: str = "tiny-mla", *,
    flood_threads: int = 3, flood_requests: int = 10, paced_requests: int = 10,
    max_tokens: int = 8, max_slots: int = 4, max_seq_len: int = 512,
    decode_chunk: int = 4, quotas: str = "alice=40,bob=100000",
) -> dict[str, float]:
    """Model-zoo + tenancy sweep (ISSUE 19; two perf_gate-floored keys):

    - `zoo_swap_in_s`: two models through ONE ModelZoo with hot=1. Model A
      boots resident, a request for parked B forces the full swap cycle
      (device_get A's tree to host, shut A down, cold-load B), then a
      request for A again pages A's PARKED HOST TREE back into HBM through
      the warmup path — that second move is the line of record: it is what
      every steady-state swap costs, with no checkpoint read in the wall.
    - `tenant_isolation`: on the re-resident A, tenant "alice" floods far
      past a tiny token-bucket quota while tenant "bob" sends paced
      traffic under an effectively unmetered one, both through the same
      admission gate the API uses. The key is bob's goodput_ratio — with
      working quotas alice 429s instead of starving bob's slots, so bob's
      tokens keep meeting the TTFT+ITL SLO.

    Also emits ungated evidence: `zoo_cold_load_s` (B's first-touch load,
    dominated by init/checkpoint), `zoo_swaps` (total residency moves),
    `tenant_a_shed` (alice's 429 count — zero means the flood never hit
    the quota and the isolation number is untested 🡒 the gate still sees
    bob's ratio, but don't trust a run with 0 sheds)."""
    import threading

    import jax
    import jax.numpy as jnp

    from llm_mcp_tpu.executor import GenerationEngine, ModelZoo

    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    out: dict[str, float] = {}
    old_quotas = os.environ.get("TPU_TENANT_QUOTAS")
    os.environ["TPU_TENANT_QUOTAS"] = quotas
    try:
        # the factory owns every construction kwarg (api/__main__.py
        # pattern); host_params=None is a cold first load, a tree is a
        # swap-in of parked weights. Each build re-reads the quota env.
        def factory(name: str, host_params):
            return GenerationEngine(
                name, params=host_params, max_slots=max_slots,
                max_seq_len=max_seq_len, dtype=dtype,
                decode_chunk=decode_chunk, seed=0,
            )

        zoo = ModelZoo(factory, hot=1, swap=True)
        zoo.register(model_a, resident=True)
        zoo.register(model_b)
        # first touch of parked B: evicts A (parks its tree in host RAM)
        # and cold-loads B — checkpoint/init cost, reported but not gated
        t0 = time.monotonic()
        eng_b = zoo.get(model_b)
        out["zoo_cold_load_s"] = round(time.monotonic() - t0, 3)
        eng_b.generate("zoo liveness probe", max_tokens=4, temperature=0.0)
        # the move of record: A back in FROM ITS PARKED HOST TREE — the
        # steady-state swap cost perf_gate ceilings at 60 s
        t0 = time.monotonic()
        eng = zoo.get(model_a)
        out["zoo_swap_in_s"] = round(time.monotonic() - t0, 3)
        eng.generate("zoo liveness probe", max_tokens=4, temperature=0.0)

        lock = threading.Lock()
        sheds = {"alice": 0, "bob": 0}
        served = {"alice": 0, "bob": 0}

        def one(tenant: str, i: int) -> None:
            shed, _retry = eng.admission_state(tenant=tenant)
            if shed:
                eng.note_shed(tenant=tenant)
                with lock:
                    sheds[tenant] += 1
                return
            eng.generate(
                f"tenant {tenant} probe {i}: count the items",
                max_tokens=max_tokens, temperature=0.0, tenant=tenant,
            )
            with lock:
                served[tenant] += 1

        def flood() -> None:
            for i in range(flood_requests):
                one("alice", i)

        t0 = time.monotonic()
        ts = [threading.Thread(target=flood) for _ in range(flood_threads)]
        for t in ts:
            t.start()
        for i in range(paced_requests):
            one("bob", i)
        for t in ts:
            t.join()
        wall = max(time.monotonic() - t0, 1e-9)

        tstats = (eng.perf_stats().get("tenants") or {})
        bob = tstats.get("bob") or {}
        out["tenant_isolation"] = round(float(bob.get("goodput_ratio", 0.0)), 3)
        out["tenant_b_goodput_tok_per_s"] = round(
            float(bob.get("goodput_tok_per_s", 0.0)), 1)
        out["tenant_a_shed"] = float(sheds["alice"])
        out["tenant_b_shed"] = float(sheds["bob"])
        out["tenant_a_served"] = float(served["alice"])
        out["tenant_b_served"] = float(served["bob"])
        out["tenant_window_s"] = round(wall, 1)
        zs = zoo.stats()
        out["zoo_swaps"] = float(
            zs["swaps_in_total"] + zs["swaps_out_total"])
        zoo.shutdown()
    finally:
        if old_quotas is None:
            os.environ.pop("TPU_TENANT_QUOTAS", None)
        else:
            os.environ["TPU_TENANT_QUOTAS"] = old_quotas
    return out


def real_ckpt_metrics(ckpt_dir: str) -> dict[str, float]:
    """Published-checkpoint secondary (VERDICT r4 #8): serve a real HF
    checkpoint dir, check output sanity, record throughput. Decoders get a
    factual-continuation probe; encoder (bert/nomic_bert) checkpoints get
    the semantic-cosine probe — the same split as the pytest half
    (tests/test_published_checkpoint.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    platform = device_platform()
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    with open(os.path.join(ckpt_dir, "config.json")) as f:
        mt = str(json.load(f).get("model_type", "")).lower()
    name = os.path.basename(ckpt_dir.rstrip("/"))
    if mt in ("bert", "nomic_bert"):
        from llm_mcp_tpu.executor import EmbeddingEngine

        eng = EmbeddingEngine(name, weights_dir=ckpt_dir, max_seq_len=512,
                              dtype=dtype)
        try:
            vecs, _ = eng.embed([
                "a cat sat on the windowsill in the sun",
                "a kitten rested by the sunny window",
                "quarterly revenue grew nine percent year over year",
            ])
            v = np.asarray(vecs)
            related, unrelated = float(v[0] @ v[1]), float(v[0] @ v[2])
            t0 = time.perf_counter()
            n = 0
            while time.perf_counter() - t0 < 5.0:
                eng.embed(["throughput probe input"])
                n += 1
            return {
                "real_ckpt_sanity": 1.0 if related > unrelated + 0.1 else 0.0,
                "real_ckpt_embeds_per_s_b1": round(
                    n / (time.perf_counter() - t0), 1
                ),
            }
        finally:
            del eng
            gc.collect()

    from llm_mcp_tpu.executor import GenerationEngine

    eng = GenerationEngine(
        name, weights_dir=ckpt_dir,
        max_slots=8, max_seq_len=512, dtype=dtype, quant="int8",
        kv_quant="int8",
    ).start()
    try:
        out = eng.generate(
            "Question: What is the capital of France?\nAnswer:",
            max_tokens=8, temperature=0.0,
        )
        sane = 1.0 if "paris" in out["text"].lower() else 0.0
        t0 = time.perf_counter()
        r = eng.generate("Write one sentence about the sea.",
                         max_tokens=64, temperature=0.0)
        dt = time.perf_counter() - t0
        return {
            "real_ckpt_sanity": sane,
            "real_ckpt_tok_per_s_b1": round(
                r["usage"]["completion_tokens"] / max(dt, 1e-9), 1
            ),
        }
    finally:
        eng.shutdown()
        gc.collect()


def client_proc(
    url: str, n: int, max_tokens: int, model: str, prompt: str,
    workload: str = "unique",
) -> None:
    """Bench client worker (separate process, pure stdlib — never imports
    jax): loops streaming chat requests, prints `TTFT <post_epoch>
    <first_delta_epoch>` per request and `WARMED` once every client thread
    has a full round-trip behind it. Runs until terminated by the parent."""
    import json as _json
    import sys as _sys
    import threading
    import urllib.error
    import urllib.request

    lock = threading.Lock()
    warmed: set[int] = set()
    announced = [False]

    def client(cid: int) -> None:
        if workload == "repetitive":
            # loop-heavy greedy completions: the self-speculative drafter's
            # best case (the completion keeps revisiting its own n-grams),
            # used by the spec sweep to measure draft-and-verify payoff
            phrase = ["alpha beta gamma", "one two three four",
                      "red green blue", "north south east west"][cid % 4]
            content = (f"{prompt} repeat the exact words '{phrase}' over and"
                       " over until you run out of room.")
            temperature = 0.0
        elif workload == "shared":
            # 90%-shared oversubscription workload (paged-KV acceptance):
            # nine of ten clients ask over the SAME long preamble — the
            # paged prefix cache pins those KV blocks instead of copying
            # rows — while every tenth client is fully unique so admission
            # keeps paying honest full prefills. Greedy, so the paged
            # path's token-identity promise is exercised at bench scale.
            preamble = prompt * 3  # well past the prefix-store minimum
            if cid % 10 == 9:
                content = (f"unshared probe {os.getpid()}-{cid}: name three"
                           f" prime numbers above {cid * 11 + 2} and stop.")
            else:
                content = f"{preamble} shared tail {cid % 10}, answer in one line."
            temperature = 0.0
        else:
            # unique per-client suffix after the shared preamble: distinct
            # prompts (honest per-request prefill work) over a shared prefix
            # (the shape of production system-prompt traffic)
            content = (f"{prompt} question {os.getpid()}-{cid}: summarize"
                       f" request number {cid * 7 + 13} in one line.")
            temperature = 0.7
        body = _json.dumps(
            {
                "model": model,
                "stream": True,
                "max_tokens": max_tokens,
                "temperature": temperature,
                "messages": [{"role": "user", "content": content}],
            }
        ).encode()
        while True:
            req = urllib.request.Request(
                url, data=body, headers={"Content-Type": "application/json"}
            )
            t0 = time.time()
            first = None
            try:
                with urllib.request.urlopen(req, timeout=900.0) as resp:
                    for raw in resp:
                        line = raw.decode("utf-8", "replace").strip()
                        if not line.startswith("data:"):
                            continue
                        payload = line[5:].strip()
                        if payload == "[DONE]":
                            break
                        if first is None:
                            evt = _json.loads(payload)
                            if evt["choices"][0]["delta"].get("content"):
                                first = time.time()
                                # report AT first-delta time: a request whose
                                # stream outlives the window must still land
                                # in the percentiles (no survivorship bias).
                                # single write + flush: concurrent client
                                # threads must not interleave mid-line
                                _sys.stdout.write(f"TTFT {t0} {first}\n")
                                _sys.stdout.flush()
            except urllib.error.HTTPError as e:
                if e.code == 429:
                    # admission shed: honor Retry-After (the KV pool's
                    # drain estimate) and report it upward — a shed is load
                    # control working, not a client failure
                    try:
                        delay = min(30.0, max(0.5, float(e.headers.get("Retry-After"))))
                    except (TypeError, ValueError):
                        delay = 1.0
                    _sys.stdout.write(f"SHED {time.time()} {delay}\n")
                    _sys.stdout.flush()
                    time.sleep(delay)
                    continue
                print(f"# bench client {cid} request failed: {e!r}", flush=True)
                time.sleep(0.5)
                continue
            except Exception as e:
                # a transient HTTP/SSE error must not kill the client for
                # the whole run — log, back off, retry
                print(f"# bench client {cid} request failed: {e!r}", flush=True)
                time.sleep(0.5)
                continue
            with lock:
                warmed.add(cid)
                if len(warmed) >= n and not announced[0]:
                    announced[0] = True
                    print("WARMED", flush=True)

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True) for i in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()  # run until the parent terminates us


def _exit_now(code: int) -> None:
    """Hard-exit after the bench line printed: lingering TPU-runtime/client
    threads (SSE handlers mid-stream, the runtime's native threads) can abort
    the interpreter during normal teardown (observed: 'FATAL: exception not
    rethrown', rc=134 AFTER a successful line) — the driver must see the rc
    that matches what was printed."""
    import sys as _s

    _s.stdout.flush()
    _s.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    import sys as _sys

    if len(_sys.argv) > 1 and _sys.argv[1] == "--client-proc":
        client_proc(
            _sys.argv[2], int(_sys.argv[3]), int(_sys.argv[4]),
            _sys.argv[5], _sys.argv[6],
            _sys.argv[7] if len(_sys.argv) > 7 else "unique",
        )
    else:
        try:
            main()
        except SystemExit as e:
            print(f"# bench failed: {e}", flush=True)
            _exit_now(1)
        _exit_now(0)
