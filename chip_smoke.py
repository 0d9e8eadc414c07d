#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python3 chip_smoke.py              # one chip: the path of record
    python3 chip_smoke.py --four-chips # tp=4 bf16, and what it is compared with

One process, started before anything else has touched JAX (a chip belongs to
one process at a time). With no arguments it boots what `python -m
llm_mcp_tpu.api` boots — GenerationEngine + EmbeddingEngine + CoreServer, for
llama-3.1-8b at full width and depth, seeded random int8 weights, int8 KV,
every default left alone — sends real HTTP through /v1/chat/completions and
/v1/embeddings, and checks from the engine that the device did the work.

It needs a TPU: under JAX_PLATFORMS=cpu, or on a machine without a chip, it
exits non-zero with one line. There is no CPU leg and no tiny model. Any phase
that fails raises, the exit code is non-zero and the last line is absent.

Last line of standard output, exactly:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Earlier lines say what is worth knowing, every number with the device it came
from.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import http.client
import json
import os
import sys
import time


@dataclasses.dataclass
class Settings:
    """What the smoke serves. The defaults are the entry point's own
    (utils/config.py); there is no option that changes them — the CPU
    rehearsal test swaps this object from inside the test."""

    model: str = "llama-3.1-8b"
    embed_model: str = "nomic-embed-text"
    quant: str = "int8"
    kv_quant: str = "int8"
    max_slots: int = 32
    max_seq_len: int = 2048
    max_tokens: int = 24
    request_timeout_s: float = 900.0


SETTINGS = Settings()

# a system prefix several KV blocks long (64-token blocks, byte tokenizer:
# one token per byte), shared by two chats so the second pins the first's
# blocks and decodes through the block-indirect (paged) arm
SHARED_PREFIX = (
    "You are the routing assistant of a fleet of accelerator hosts. Answer "
    "briefly, name the device you would pick and the reason, never invent a "
    "device that is not in the catalog, and say so when no device fits. "
    "The catalog follows. "
    + " ".join(f"device-{i:02d}: v5e, 16 GB, slots 32, load {i * 7 % 10}/10." for i in range(12))
)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- phases -----------------------------------------------------------------
# Each takes the shared context dict, raises on failure, and returns nothing.
# main() runs them in order; nothing catches what they raise.


def phase_device(ctx: dict) -> None:
    """Fail at once unless JAX's first device is a TPU. First touch of JAX."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX reports platform={d0.platform!r} "
            f"({len(devs)} device(s)). No CPU leg."
        )
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string, not a phase
        libtpu = "unknown"
    stats = d0.memory_stats() or {}
    ctx["device"] = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}
    ctx["tag"] = f"[{d0.device_kind} x{len(devs)}]"
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  libtpu {libtpu}  "
        f"python {sys.version.split()[0]}")
    say(f"{ctx['tag']} platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devs)} HBM bytes_limit={stats.get('bytes_limit', 'n/a')}")


def phase_cache(ctx: dict) -> None:
    """The compile cache: JAX_COMPILATION_CACHE_DIR where set, else the fixed
    <checkout>/.jax_cache. A directory that cannot be used is said aloud."""
    from llm_mcp_tpu.utils import config as ucfg

    cache_dir = ucfg.enable_compile_cache()
    n = len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0
    say(f"compile cache: dir={cache_dir} entries_at_start={n} "
        f"failures={ucfg.compile_cache_failures} "
        f"(JAX_COMPILATION_CACHE_DIR={'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    if ucfg.compile_cache_failures:
        say(f"WARNING: compile cache at {ucfg.compile_cache_path()} could not be "
            "used; this boot compiles everything cold")
    ctx["cache_entries_at_start"] = n

    # hits and misses from JAX's own counters, not from how long a compile
    # took (the ledger's hit/miss is a wall-time guess, and loading an 8B
    # executable takes seconds)
    import jax

    events = ctx["cache_events"] = {"cache_hits": 0, "cache_misses": 0}

    def on_event(event: str, **_) -> None:
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in events:
            events[name] += 1

    jax.monitoring.register_event_listener(on_event)


def _engine_kwargs(cfg) -> dict:
    """What api/__main__.py hands GenerationEngine besides the model, the mesh
    and the quantization: the smoke's sizes, the entry point's config."""
    import jax.numpy as jnp

    return dict(
        max_slots=SETTINGS.max_slots,
        max_seq_len=SETTINGS.max_seq_len,
        dtype=jnp.bfloat16,
        prefill_chunk=cfg.tpu_prefill_chunk,
        decode_compact=cfg.tpu_decode_compact,
        prompt_cache_mb=cfg.tpu_prompt_cache_mb,
        prefill_buckets=cfg.tpu_prefill_buckets,
        target_ttft_ms=cfg.tpu_target_ttft_ms,
    )


def phase_boot(ctx: dict) -> None:
    """Engines + server, the way api/__main__.py builds them."""
    import jax.numpy as jnp

    from llm_mcp_tpu.api.server import CoreServer
    from llm_mcp_tpu.executor import EmbeddingEngine, GenerationEngine
    from llm_mcp_tpu.state.db import Database
    from llm_mcp_tpu.utils.config import Config

    s = SETTINGS
    cfg = Config()
    t0 = time.perf_counter()
    gen = GenerationEngine(
        s.model, quant=s.quant, kv_quant=s.kv_quant, **_engine_kwargs(cfg)
    ).start()
    t_gen = time.perf_counter() - t0
    emb = EmbeddingEngine(
        s.embed_model,
        max_seq_len=min(s.max_seq_len, 8192),
        dtype=jnp.bfloat16,
        quant=cfg.tpu_embed_quant,
    )
    t1 = time.perf_counter()
    srv = CoreServer(
        cfg,
        db=Database(":memory:"),
        gen_engines={s.model: gen},
        embed_engines={s.embed_model: emb},
    ).start("127.0.0.1", 0)  # boot_warmup: the critical executables compile here
    ctx.update(gen=gen, emb=emb, srv=srv, port=srv.api.port, prefill_chunk=cfg.tpu_prefill_chunk)
    c = gen.cfg
    say(f"{ctx['tag']} model={s.model} layers={c.n_layers} hidden={c.dim} "
        f"heads={c.n_heads}/{c.n_kv_heads} head_dim={c.resolved_head_dim} "
        f"vocab={c.vocab_size} quant={gen.quant or 'none'} kv_quant={gen.kv_quant or 'none'} "
        f"slots={gen.max_slots} seq={gen.max_seq_len} "
        f"tokenizer={type(gen.tokenizer).__name__} (no checkpoint: random weights, seed 0)")
    say(f"{ctx['tag']} boot: engine {t_gen:.1f} s, server+critical warmup "
        f"{time.perf_counter() - t1:.1f} s, total {time.perf_counter() - t0:.1f} s; "
        f"warmup={gen.warmup_stats().get('state')}")
    ctx["boot_s"] = time.perf_counter() - t0


def _chat(port: int, model: str, messages: list[dict], *, max_tokens: int,
          timeout: float, temperature: float = 0.0) -> dict:
    """One streaming chat over real HTTP. Returns what came back; raises on
    an error event or a stream that does not end in [DONE]."""
    body = json.dumps({
        "model": model, "messages": messages, "stream": True,
        "max_tokens": max_tokens, "temperature": temperature,
    })
    t0 = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/chat/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"chat: HTTP {resp.status}: {resp.read()[:300]!r}")
        text, finish, usage, first_s, done = [], None, {}, None, False
        for raw in resp:
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                done = True
                break
            evt = json.loads(data)
            if "error" in evt:
                raise RuntimeError(f"chat: error event: {evt['error']}")
            choice = evt["choices"][0]
            if choice["delta"].get("content") is not None:
                if first_s is None:
                    first_s = time.perf_counter() - t0
                text.append(choice["delta"]["content"])
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
            usage = evt.get("usage") or usage
    finally:
        conn.close()
    if not done:
        raise RuntimeError("chat: stream ended without data: [DONE]")
    return {"text": "".join(text), "finish_reason": finish, "usage": usage,
            "first_token_s": first_s, "total_s": time.perf_counter() - t0}


def _check_chat(name: str, out: dict) -> None:
    if out["finish_reason"] not in ("stop", "length"):
        raise AssertionError(f"{name}: finish_reason={out['finish_reason']!r}")
    if not out["usage"].get("completion_tokens", 0) > 0:
        raise AssertionError(f"{name}: no completion tokens: usage={out['usage']}")


def _tap_served(gen) -> dict:
    """Record the token ids the engine emits, request by request: {request id:
    (prompt ids, emitted ids)}. What HTTP carries is text, and the byte
    tokenizer's text does not give the ids back, so they are read where the
    engine's loop emits them. `del gen._process_token` ends the tap."""
    served: dict[str, tuple[list[int], list[int]]] = {}
    emit = gen._process_token

    def tap(slot, tok, pos):
        served.setdefault(slot.req.request_id, (list(slot.req.prompt_ids), []))[1].append(int(tok))
        return emit(slot, tok, pos)

    gen._process_token = tap
    return served


def _served_for(ctx: dict, text: str) -> tuple[list[int], list[int]]:
    """(prompt ids, emitted ids) of the one served request whose prompt holds `text`."""
    decode = ctx["gen"].tokenizer.decode
    hits = [rec for rec in ctx["served"].values() if text in decode(rec[0])]
    if len(hits) != 1:
        raise AssertionError(f"{len(hits)} served requests hold {text!r}, want 1")
    return hits[0]


def _hold_to_reference(ctx: dict, name: str, prompt: list[int], emitted: list[int],
                       tol_rel: float, fetch=lambda x: x) -> None:
    """Every token the engine served must be the plain float32 forward's
    greedy choice among the tokens the engine may emit, or sit within
    `tol_rel` of that choice's logit (of the row's max |logit|): the prompt
    and what was served go through models/reference.py in one teacher-forced
    pass over the engine's own weights. Random weights make near-ties, and
    int8 KV, int8 dots and bf16 move a logit by a few percent, so identity is
    not the bar; another request's logits, a stale token or a lost KV row
    miss by the spread of the logits themselves."""
    import numpy as np

    from llm_mcp_tpu.models.reference import llama_forward_layerwise

    gen = ctx["gen"]
    if not emitted:
        raise AssertionError(f"{name}: nothing was served")
    mask = gen._allowed_mask
    allowed = np.arange(gen.cfg.vocab_size) if mask is None else np.flatnonzero(np.asarray(mask))
    seq = prompt + emitted[:-1]
    rows = np.arange(len(prompt) - 1, len(seq))
    # causal: what follows a row does not move it, so the sequence is padded
    # to a round length and prompts of about one size share their compiles
    seq = np.asarray(seq + [0] * (-len(seq) % 128), np.int32)
    t0 = time.perf_counter()
    ref = np.asarray(llama_forward_layerwise(
        gen.cfg, gen.params, seq, fetch, rows=rows, cols=allowed), np.float32)
    worst, flips = 0.0, 0
    for k, tok in enumerate(emitted):
        col = np.flatnonzero(allowed == tok)
        if not len(col):
            raise AssertionError(f"{name}: served token {tok} at step {k} is not one the engine may emit")
        scale = float(np.max(np.abs(ref[k]))) or 1.0
        regret = float(np.max(ref[k]) - ref[k, col[0]])
        if not np.isfinite(ref[k]).all() or regret > tol_rel * scale:
            raise AssertionError(
                f"{name}: served token {tok} at step {k} is {regret:.4g} under the "
                f"reference's choice {int(allowed[np.argmax(ref[k])])} "
                f"(row max |logit| {scale:.3g}, tolerance {tol_rel * scale:.3g})")
        worst = max(worst, regret / scale)
        flips += regret > 0
    say(f"{ctx['tag']} reference {name!r}: {len(emitted)} served tokens over a "
        f"{len(prompt)}-token prompt, {len(emitted) - flips} the float32 forward's own choice, "
        f"{flips} within tolerance, worst {worst:.4f} of the row's max |logit| "
        f"(tolerance {tol_rel}); forward {time.perf_counter() - t0:.1f} s")


def phase_chat(ctx: dict) -> None:
    """A handful of streaming chats: one cold, then five at once — among them
    a second sharer of the long system prefix and one prompt longer than
    TPU_PREFILL_CHUNK — then a third sharer, which hits the prefix cache."""
    s, port = SETTINGS, ctx["port"]
    kw = dict(max_tokens=s.max_tokens, timeout=s.request_timeout_s)
    sys_msg = {"role": "system", "content": SHARED_PREFIX}
    ctx["served"] = _tap_served(ctx["gen"])  # phase_served_reference reads it

    first = _chat(port, s.model, [sys_msg, {"role": "user", "content": "Which device for a 7B chat model?"}], **kw)
    _check_chat("first chat", first)
    ctx["first_token_cold_s"] = first["first_token_s"]
    say(f"{ctx['tag']} first chat (cold, shared prefix A): first token "
        f"{first['first_token_s']:.1f} s, total {first['total_s']:.1f} s, usage={first['usage']}")

    long_user = "Summarize this log. " + " ".join(
        f"line {i}: slot {i % 32} admitted, prefill {64 + i % 7} tokens, ttft {120 + i * 3 % 90} ms."
        for i in range(ctx["prefill_chunk"] // 16)
    )
    prompts = [
        ("shared prefix B", [sys_msg, {"role": "user", "content": "And which device for an embedding job?"}]),
        ("long prompt", [{"role": "user", "content": long_user}]),
        ("short 1", [{"role": "user", "content": "Say hello."}]),
        ("short 2", [{"role": "user", "content": "Count to five."}]),
        ("short 3", [{"role": "user", "content": "Name a color."}]),
    ]
    n_long = len(ctx["gen"].tokenizer.encode(long_user))
    if n_long <= ctx["prefill_chunk"]:
        raise AssertionError(f"long prompt is {n_long} tokens, chunk is {ctx['prefill_chunk']}")
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        futs = {name: pool.submit(_chat, port, s.model, msgs, **kw) for name, msgs in prompts}
        for name, fut in futs.items():
            out = fut.result()
            _check_chat(name, out)
            say(f"{ctx['tag']} chat {name!r}: first token {out['first_token_s']:.1f} s, "
                f"total {out['total_s']:.1f} s, finish={out['finish_reason']}, usage={out['usage']}")
    say(f"{ctx['tag']} long prompt: {n_long} tokens > prefill chunk {ctx['prefill_chunk']}")

    # the engine stores a prefix the second time traffic shares it and pins
    # it the third: this sharer's first blocks resolve through the prefix
    # pool, so its decode rounds take the block-indirect (paged) arm
    third = _chat(port, s.model, [sys_msg, {"role": "user", "content": "And for a batch of 30 short prompts?"}], **kw)
    _check_chat("shared prefix C", third)
    say(f"{ctx['tag']} chat 'shared prefix C': first token {third['first_token_s']:.1f} s, "
        f"total {third['total_s']:.1f} s, usage={third['usage']}, "
        f"prefix hits so far={ctx['gen'].prefix_cache_hits}")
    del ctx["gen"]._process_token  # the tap ends with the traffic it records


# the served tokens against the float32 forward, as a share of a row's max
# |logit|: int8 x int8 dots, int8 KV and bf16 activations over 32 layers on
# one side, float32 on the other. The v5e showed 3 of 72 tokens off the
# reference's choice, the worst by 0.0151 (PR 21); the bar is the four-chip
# comparison's own (LOGIT_ATOL_REL), three times that
SERVED_TOL_REL = 0.05


def phase_served_reference(ctx: dict) -> None:
    """What the burst above served, held to the reference: the long prompt
    (chunked ragged prefill, cold and among four others), the second sharer
    of the system prefix (cold, concurrent) and the third (prefix hit: its
    past streams block-indirect from the pool)."""
    for name, text in (
        ("long prompt", "Summarize this log."),
        ("shared prefix B", "And which device for an embedding job?"),
        ("shared prefix C", "And for a batch of 30 short prompts?"),
    ):
        prompt, emitted = _served_for(ctx, text)
        _hold_to_reference(ctx, name, prompt, emitted, SERVED_TOL_REL)


def phase_determinism(ctx: dict) -> None:
    """Greedy decoding of the same prompt twice gives the same tokens. The
    prompt is shorter than one KV block, and the two run one after the other,
    so both take the same executables on the same inputs: this is about the
    sampler and the slot's reuse. Whether what was served under load is RIGHT
    is phase_served_reference's question."""
    s = SETTINGS
    msgs = [{"role": "user", "content": "Repeat after me: ok."}]
    a, b = (
        _chat(ctx["port"], s.model, msgs, max_tokens=s.max_tokens,
              timeout=s.request_timeout_s, temperature=0.0)
        for _ in range(2)
    )
    _check_chat("greedy 1", a)
    _check_chat("greedy 2", b)
    if a["text"] != b["text"] or a["usage"] != b["usage"]:
        raise AssertionError(f"greedy decoding differed: {a['text']!r} vs {b['text']!r}")
    say(f"{ctx['tag']} greedy repeat: identical ({a['usage'].get('completion_tokens')} tokens, "
        f"warm first token {b['first_token_s'] * 1e3:.0f} ms)")


def phase_embeddings(ctx: dict) -> None:
    s = SETTINGS
    conn = http.client.HTTPConnection("127.0.0.1", ctx["port"], timeout=s.request_timeout_s)
    try:
        conn.request("POST", "/v1/embeddings",
                     json.dumps({"model": s.embed_model, "input": ["one chip, one process"]}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"embeddings: HTTP {resp.status}: {raw[:300]!r}")
    vec = json.loads(raw)["data"][0]["embedding"]
    want = ctx["emb"].cfg.dim
    if len(vec) != want or not all(x == x and abs(x) < 1e4 for x in vec):
        raise AssertionError(f"embedding: {len(vec)} values (want {want}) or non-finite")
    say(f"{ctx['tag']} embedding: {len(vec)} finite values from {s.embed_model}")


def kernel_parity_cases(cfg, S: int, bt: int):
    """Small inputs at the model's own widths for the serving kernels and the
    plain-JAX math each is held to. Yields (name, kernel_fn, reference_fn,
    args, tolerance). These references are kernels/attention.py's own XLA
    arms (what the CPU runs and what a failed shape gate falls to), so this
    says kernel and fallback agree on the chip; the code that shares nothing
    with them is models/reference.py, in phase_served_reference. Tolerances
    are twice what the v5e showed (PR 21: 2 bf16 steps for the decode arms,
    0.0143 of the reference max; 1 step for ragged prefill, 0.0037)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_mcp_tpu.kernels import attention as A
    from llm_mcp_tpu.models.llama import fuse_prompt_kv

    Hkv, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.resolved_head_dim
    L, B, Ba = 2, 4, 8
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16

    def rnd(*shape):
        return jnp.asarray(rng.standard_normal(shape), bf)

    ck = fuse_prompt_kv(rnd(L, B, Hkv, S, hd), rnd(L, B, Hkv, S, hd), scale_dtype=bf)
    nbs = S // bt
    pool = fuse_prompt_kv(rnd(L, 3, Hkv, bt, hd), rnd(L, 3, Hkv, bt, hd), scale_dtype=bf)
    q, nk, nv = rnd(Ba, Hkv, G, hd), rnd(Ba, Hkv, hd), rnd(Ba, Hkv, hd)
    ids = jnp.asarray([0, 1, 2, 3, 3, 3, 3, 3], jnp.int32)
    # a short row, a mid-block row, a full row, a row past several blocks; pad rows parked
    lens = jnp.asarray([5, bt + 3, S - 1, 3 * bt, S, S, S, S], jnp.int32)
    tbl = np.arange(B * nbs, dtype=np.int32).reshape(B, nbs)
    tbl[1, 0] = B * nbs + 1  # row 1's first block lives in the prefix pool
    tbl[3, 1] = B * nbs + 2
    tbl[3, 2] = 0 * nbs + 2  # and one of row 3's in another slot's arena home
    tbl = jnp.asarray(tbl)
    layer = jnp.int32(1)
    sc = hd**-0.5

    live = 4  # rows past these are parked pads: their output is discarded

    def ref(q, nk, nv, ck, tables=None, pool=None):
        return A._decode_attend_q8_fallback(
            q, nk, nv, ck, {}, layer, lens, sc, ids, tables, pool)[:live]

    def q8(mode):
        """decode_attend_q8 under one arm: the dispatcher reads its mode at
        TRACE time inside a jitted function, so each arm gets a fresh trace
        and leaves none behind."""
        def kern(q, nk, nv, ck, *paged):
            os.environ["LLM_MCP_TPU_Q8_DECODE"] = mode
            A.decode_attend_q8.clear_cache()
            try:
                kw = dict(zip(("block_tables", "pool_k"), paged))
                return A.decode_attend_q8(
                    q, nk, nv, ck, {}, layer, lens, slot_ids=ids, **kw)[:live]
            finally:
                os.environ.pop("LLM_MCP_TPU_Q8_DECODE", None)
                A.decode_attend_q8.clear_cache()
        return kern

    for mode in ("whole", "blocked", "auto"):
        yield f"decode_attend_q8[{mode}]", q8(mode), ref, (q, nk, nv, ck), 0.03
    yield "decode_attend_q8[paged]", q8("paged"), ref, (q, nk, nv, ck, tbl, pool), 0.03

    # append: the kernel's in-place tile rewrite against the XLA scatter
    nkL, nvL = rnd(L, Ba, Hkv, hd), rnd(L, Ba, Hkv, hd)
    alens = jnp.asarray([5, bt + 3, S - 1, 3 * bt, S, S, S, S], jnp.int32)

    def append_kernel(ck, nk, nv):
        out, _ = A.append_kv_q8(jax.tree.map(jnp.copy, ck), {}, nk, nv, alens, slot_ids=ids)
        return jnp.concatenate([out["q"].astype(jnp.float32).reshape(-1),
                                out["s"].astype(jnp.float32).reshape(-1)])

    def append_ref(ck, nk, nv):
        out, _ = jax.jit(A.append_kv_q8_reference)(ck, {}, nk, nv, alens, slot_ids=ids)
        return jnp.concatenate([out["q"].astype(jnp.float32).reshape(-1),
                                out["s"].astype(jnp.float32).reshape(-1)])
    # same bytes, but for a rounding tie two differently fused programs may
    # break differently: one int8 step
    yield "append_kv_q8", append_kernel, append_ref, (ck, nkL, nvL), 1.0 / 127

    # ragged prefill: three packed rows (one with a paged prefix, one empty)
    R, T = 4, 64
    lens_r = [20, 0, 30, 6]
    offs = np.zeros(R + 1, np.int32)
    offs[1:] = np.cumsum(lens_r)
    rowids = np.concatenate([np.full(n, r, np.int32) for r, n in enumerate(lens_r)]
                            + [np.full(T - offs[-1], R, np.int32)])
    starts = jnp.asarray([bt + 3, 0, 3 * bt, 0], jnp.int32)
    slots = jnp.asarray([1, 2, 3, 0], jnp.int32)
    qr, ks, vs = rnd(T, Hkv, G, hd), rnd(T, Hkv, hd), rnd(T, Hkv, hd)

    def rag(impl):
        def f(qr, ks, vs, ck, tables, pool):
            out = A.ragged_prefill_attend_q8(
                qr, ks, vs, ck, layer, jnp.asarray(rowids), jnp.asarray(offs), slots,
                starts, block_tables=tables, pool=pool, impl=impl)
            return out[: int(offs[-1])]
        return f
    yield "ragged_prefill_attend_q8", rag("kernel"), rag("xla"), (qr, ks, vs, ck, tbl, pool), 0.008


def phase_kernel_parity(ctx: dict) -> None:
    """The serving kernels against the same module's XLA arms, on this
    device, at the model's own widths on a small input."""
    import jax.numpy as jnp

    gen = ctx["gen"]
    bt = gen._paging.block_tokens
    for name, kern, ref, args, tol in kernel_parity_cases(gen.cfg, gen.max_seq_len, bt):
        got = jnp.asarray(kern(*args), jnp.float32)
        want = jnp.asarray(ref(*args), jnp.float32)
        if got.shape != want.shape or not bool(jnp.isfinite(got).all()):
            raise AssertionError(f"{name}: shape {got.shape} vs {want.shape}, or non-finite")
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        say(f"{ctx['tag']} kernel parity {name} vs the module's own XLA arm: "
            f"max|diff|={err:.4g} (ref max {scale:.3g}, tol {tol * scale:.3g})")
        if err > tol * scale:
            raise AssertionError(f"{name}: kernel and reference disagree by {err} (> {tol * scale})")


def _compiled_step_text(gen, phase: str, key: tuple) -> str:
    """Text of a compiled step program the ledger says ran."""
    return gen.warmup_lower(phase, key).compile().as_text()


def phase_engine_checks(ctx: dict) -> None:
    """From the engine, not from the environment: the device did the work."""
    from llm_mcp_tpu.kernels import attention as A
    from llm_mcp_tpu.telemetry import recorder

    gen = ctx["gen"]
    problems = []
    if gen.attn_impl != "pallas":
        problems.append(f"attn_impl={gen.attn_impl!r}")
    if gen.decode_impl != "pallas":
        problems.append(f"decode_impl={gen.decode_impl!r}")
    if gen._ragged_impl != "kernel":
        problems.append(f"ragged impl={gen._ragged_impl!r}")
    if gen._phys is None:
        problems.append("physical paging is off")
    if gen._thread is None or not gen._thread.is_alive():
        problems.append("engine thread is not alive")
    if gen.stalled or gen.dead:
        problems.append(f"stalled={gen.stalled} dead={gen.dead!r}")
    if A.reference_falls:
        problems.append(f"kernel-to-reference falls: {A.reference_falls}")
    falls = recorder.get_recorder().snapshot(etype="kernel_fall")
    if falls:
        problems.append(f"kernel_fall events: {falls[:3]}")
    if gen.prefix_cache_hits < 1:
        problems.append("no prefix-cache hit: the shared system prefix did not pin")
    table = gen._ledger.table()
    ran = {row["phase"] for row in table}
    if "decode" not in ran and "fused_rag" not in ran:
        problems.append(f"no decode executable in the compile ledger: {sorted(ran)}")
    if not ran & {"admit", "pf_rag", "fused_rag", "chunk"}:
        problems.append(f"no prefill executable in the compile ledger: {sorted(ran)}")
    if not ran & {"pf_rag", "fused_rag"}:
        problems.append(f"ragged prefill never dispatched: {sorted(ran)}")
    if problems:
        raise AssertionError("; ".join(problems))
    def keys(phase):
        return [gen.parse_ledger_key(r["key"]) for r in table if r["phase"] == phase]

    def kernels(phase, key):
        return _compiled_step_text(gen, phase, key).count("tpu_custom_call")

    key = (keys("decode") or [(min(8, gen.max_slots), True, True)])[0]
    n_calls = kernels("decode", key)
    if n_calls < 1:
        raise AssertionError(f"no tpu_custom_call in the compiled decode step {key}")
    # the ragged kernel too, in the step programs that ran it: alone in the
    # standalone prefill, and on top of the decode round's kernels in the
    # fused step
    for rkey in keys("pf_rag")[:1]:
        n = kernels("pf_rag", rkey)
        if n < 1:
            raise AssertionError(f"no tpu_custom_call in the compiled ragged prefill step {rkey}")
        say(f"{ctx['tag']} ragged prefill step {rkey}: {n} tpu_custom_call")
    for fkey in keys("fused_rag")[:1]:
        n, base = kernels("fused_rag", fkey), kernels("decode", (fkey[0], fkey[1], fkey[4]))
        if n <= base:
            raise AssertionError(
                f"fused step {fkey} holds {n} tpu_custom_call, its decode round alone {base}: "
                "no ragged kernel in it")
        say(f"{ctx['tag']} fused decode+ragged step {fkey}: {n} tpu_custom_call "
            f"({base} of them the decode round's)")
    st = gen._ledger.stats()
    serve = [r for r in table if r.get("by_src", {}).get("serve")]
    say(f"{ctx['tag']} attn_impl={gen.attn_impl} decode_impl={gen.decode_impl} "
        f"ragged={gen._ragged_impl} paged=physical thread=alive stalled={gen.stalled} "
        f"kernel falls=0 prefix hits={gen.prefix_cache_hits} "
        f"decode step {key}: {n_calls} tpu_custom_call")
    say(f"{ctx['tag']} executables compiled: {st['shapes']} shapes, {st['entries']} ledger "
        f"entries ({st['hits']} under the cache-hit threshold, {st['misses']} over), "
        f"{st['total_s']:.1f} s in compiles, by source {st['by_src']}; "
        f"{len(serve)} shapes first compiled on the serve path")
    for row in table[:8]:
        say(f"{ctx['tag']}   {row['phase']} {row['key']}: {row['total_s']:.1f} s {row.get('by_src', {})}")
    ctx["compile_stats"] = st


def phase_shutdown(ctx: dict) -> None:
    import jax

    ctx["srv"].shutdown()
    stats = jax.devices()[0].memory_stats() or {}
    say(f"{ctx['tag']} peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
        f"bytes_in_use={stats.get('bytes_in_use', 'n/a')} of bytes_limit={stats.get('bytes_limit', 'n/a')}")
    from llm_mcp_tpu.utils import config as ucfg

    cache_dir = ucfg.compile_cache_dir
    n = len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0
    ev = ctx["cache_events"]
    say(f"compile cache: entries_at_end={n} (started with {ctx.get('cache_entries_at_start', 0)}); "
        f"this run: {ev['cache_hits']} hits, {ev['cache_misses']} misses (jax's own counters)")
    say(f"{ctx['tag']} summary: boot {ctx['boot_s']:.1f} s, first token cold "
        f"{ctx['first_token_cold_s']:.1f} s, wall {time.perf_counter() - ctx['t_start']:.1f} s "
        "(one run by the builder's script, not a benchmark)")


ONE_CHIP_PHASES = (
    phase_device, phase_cache, phase_boot, phase_chat, phase_served_reference,
    phase_determinism, phase_embeddings, phase_kernel_parity, phase_engine_checks,
    phase_shutdown,
)


# -- the four-chip option ----------------------------------------------------


def phase_four_boot(ctx: dict) -> None:
    """llama-3.1-8b in bf16 — 16 GB of weights, which one 16 GB chip cannot
    hold — on a tp=4 GenerationEngine in this one process."""
    import jax

    from llm_mcp_tpu.api.server import CoreServer
    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.parallel import distributed
    from llm_mcp_tpu.state.db import Database
    from llm_mcp_tpu.utils.config import Config

    if len(jax.devices()) != 4:
        raise SystemExit(f"chip_smoke --four-chips: needs 4 chips, JAX reports {len(jax.devices())}")
    s = SETTINGS
    cfg = Config()
    t0 = time.perf_counter()
    mesh = distributed.make_global_mesh("tp=4")
    gen = GenerationEngine(s.model, mesh=mesh, **_engine_kwargs(cfg)).start()
    srv = CoreServer(cfg, db=Database(":memory:"), gen_engines={s.model: gen},
                     embed_engines={}).start("127.0.0.1", 0)
    ctx.update(gen=gen, srv=srv, port=srv.api.port)
    ctx["boot_s"] = time.perf_counter() - t0
    c = gen.cfg
    say(f"{ctx['tag']} model={s.model} bf16 tp=4: layers={c.n_layers} hidden={c.dim} "
        f"heads={c.n_heads}/{c.n_kv_heads} vocab={c.vocab_size} slots={gen.max_slots} "
        f"seq={gen.max_seq_len}; boot {ctx['boot_s']:.1f} s")
    # said aloud, not hidden: no Pallas kernel runs under a mesh
    say(f"{ctx['tag']} attn_impl={gen.attn_impl} decode_impl={gen.decode_impl} "
        f"ragged={gen._ragged_impl or 'off'}: sharded engines take the XLA attention "
        "path (kernels/attention.py:resolve_attn_impl returns 'xla' for any mesh); "
        "no tpu_custom_call runs in this mode")


def phase_four_shares(ctx: dict) -> None:
    """Every chip holds a comparable share of the bytes: of the engine's own
    arrays (weights + KV cache, counted shard by shard) and, where the
    backend reports it, of `bytes_in_use`."""
    import jax

    gen = ctx["gen"]
    held = {d.id: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves((gen.params, gen._ck, gen._cv)):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()}
    for d in jax.devices():
        say(f"{ctx['tag']} device {d.id}: weights+cache shards={held[d.id]} "
            f"({held[d.id] / 2**30:.2f} GiB) bytes_in_use={in_use[d.id]}")
    for name, per_dev in (("shard bytes", held), ("bytes_in_use", in_use)):
        vals = [v for v in per_dev.values() if v is not None]
        if vals and (min(vals) <= 0 or max(vals) > 1.5 * min(vals)):
            raise AssertionError(f"uneven {name} across chips: {per_dev}")
    total = sum(held.values())
    say(f"{ctx['tag']} total weights+cache {total / 2**30:.2f} GiB over {len(held)} chips")


def phase_four_chat(ctx: dict) -> None:
    s = SETTINGS
    kw = dict(max_tokens=s.max_tokens, timeout=s.request_timeout_s)
    prompts = [
        [{"role": "system", "content": SHARED_PREFIX}, {"role": "user", "content": "Which device?"}],
        [{"role": "user", "content": "Say hello."}],
        [{"role": "user", "content": "Count to five."}],
    ]
    first = _chat(ctx["port"], s.model, prompts[0], **kw)
    _check_chat("tp=4 chat 0", first)
    ctx["first_token_cold_s"] = first["first_token_s"]
    say(f"{ctx['tag']} tp=4 chat 0: first token {first['first_token_s']:.1f} s (cold), usage={first['usage']}")
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        for i, fut in enumerate([pool.submit(_chat, ctx["port"], s.model, m, **kw) for m in prompts[1:]]):
            out = fut.result()
            _check_chat(f"tp=4 chat {i + 1}", out)
            say(f"{ctx['tag']} tp=4 chat {i + 1}: first token {out['first_token_s']:.1f} s, usage={out['usage']}")


REFERENCE_PROMPT = "The quick brown fox jumps over the lazy dog."
REFERENCE_TOKENS = 4
# bf16 weights and activations, reductions split four ways on the mesh and
# not split in the reference: logits agree to bf16 rounding accumulated over
# 32 layers, not bitwise
LOGIT_ATOL_REL = 0.05


def phase_four_reference(ctx: dict) -> None:
    """What the mesh is compared with: the same weights, read back from the
    sharded tree (never re-created: born-sharded and eager init differ by an
    ULP), taken through the plain float32 forward one layer at a time on ONE
    device (models/reference.py) over one short prompt.

    Two things are held to it. The SERVED path: the prompt goes through the
    tp=4 engine as raw tokens with greedy decoding — its admit and decode step
    programs, the ones that answered the chats above — and every token it
    emits must be the reference's choice among the tokens the engine may
    emit, or sit inside the tolerance. And the numbers: the model's own
    prefill over the sharded params, teacher-forced along the served tokens,
    must give the reference's logits at every step within the same
    tolerance. One reference pass scores the whole sequence (causal, so its
    row t is what it would have predicted after t tokens)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_mcp_tpu.models.llama import llama_prefill
    from llm_mcp_tpu.models.reference import llama_forward_layerwise

    gen = ctx["gen"]
    cfg = gen.cfg
    ids = gen.tokenizer.encode(REFERENCE_PROMPT)
    n0, S = len(ids), 64
    assert n0 + REFERENCE_TOKENS <= S

    # served side: the engine's step programs
    t0 = time.perf_counter()
    served = _tap_served(gen)
    out = gen.generate(REFERENCE_PROMPT, max_tokens=REFERENCE_TOKENS, temperature=0.0)
    del gen._process_token
    (prompt, emitted), = served.values()
    if prompt != ids or not emitted:
        raise AssertionError(f"engine served {len(emitted)} tokens over {len(prompt)} prompt ids, want {n0}")
    say(f"{ctx['tag']} tp=4 engine, raw prompt, greedy: tokens {emitted} "
        f"finish={out['finish_reason']} in {time.perf_counter() - t0:.1f} s")
    seq = list(ids) + emitted

    # mesh side, the numbers: the model's own prefill over the sharded params,
    # one executable for every step (fixed S, the length is data)
    prefill = jax.jit(lambda p, t, n: llama_prefill(cfg, p, t, n, attn_impl="xla")[0])

    def mesh_logits(tokens: list[int]) -> np.ndarray:
        toks = np.zeros((1, S), np.int32)
        toks[0, : len(tokens)] = tokens
        with gen.mesh:
            logits = prefill(gen.params, jnp.asarray(toks),
                             jnp.asarray([len(tokens)], jnp.int32))
        return np.asarray(jax.device_get(logits), np.float32)[0]

    t0 = time.perf_counter()
    steps = [mesh_logits(seq[: n0 + k]) for k in range(len(emitted))]
    t_mesh = time.perf_counter() - t0

    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def fetch(x):  # sharded array -> host -> device 0
        return jax.device_put(np.asarray(jax.device_get(x)), one)

    t0 = time.perf_counter()
    ref = np.asarray(llama_forward_layerwise(
        cfg, gen.params, np.asarray(seq[:-1], np.int32), fetch), np.float32)
    say(f"{ctx['tag']} reference: mesh prefill {len(steps)} steps {t_mesh:.1f} s, "
        f"off-mesh float32 forward over {len(seq) - 1} tokens {time.perf_counter() - t0:.1f} s")

    mask = gen._allowed_mask
    allowed = np.ones(cfg.vocab_size, bool) if mask is None else np.asarray(mask)
    for step, (lm, tok) in enumerate(zip(steps, emitted)):
        lr = ref[n0 - 1 + step]
        scale = float(np.max(np.abs(lr))) or 1.0
        err = float(np.max(np.abs(lm - lr)))
        choice = int(np.argmax(np.where(allowed, lr, -np.inf)))
        say(f"{ctx['tag']} reference step {step}: max|logit diff|={err:.4g} "
            f"(ref max {scale:.3g}, tol {LOGIT_ATOL_REL * scale:.3g}); "
            f"engine served {tok}, off-mesh choice {choice}")
        if not np.isfinite(lm).all() or err > LOGIT_ATOL_REL * scale:
            raise AssertionError(f"tp=4 logits disagree with the off-mesh forward: {err}")
        if not allowed[tok]:
            raise AssertionError(f"served token {tok} is not one the engine may emit")
        if tok != choice:
            # near-tied random logits can flip an argmax inside the
            # tolerance; it is a different result only if the margin is real
            margin = float(lr[choice] - lr[tok])
            if margin > LOGIT_ATOL_REL * scale:
                raise AssertionError(
                    f"served token differs beyond tolerance at step {step}: "
                    f"{tok} vs {choice} (margin {margin})")
            say(f"{ctx['tag']}   served token inside tolerance of the choice (margin {margin:.4g})")


def phase_four_shutdown(ctx: dict) -> None:
    import jax

    ctx["srv"].shutdown()
    for d in jax.devices():
        st = d.memory_stats() or {}
        say(f"{ctx['tag']} device {d.id}: peak_bytes_in_use={st.get('peak_bytes_in_use', 'n/a')}")
    ev = ctx["cache_events"]
    say(f"compile cache this run: {ev['cache_hits']} hits, {ev['cache_misses']} misses (jax's own counters)")
    say(f"{ctx['tag']} summary: boot {ctx['boot_s']:.1f} s, first token cold "
        f"{ctx['first_token_cold_s']:.1f} s, wall {time.perf_counter() - ctx['t_start']:.1f} s "
        "(one run by the builder's script, not a benchmark)")


FOUR_CHIP_PHASES = (
    phase_device, phase_cache, phase_four_boot, phase_four_shares, phase_four_chat,
    phase_four_reference, phase_four_shutdown,
)


def run(phases) -> dict:
    """Run the phases in order. Nothing here catches a failure: the first
    phase that raises ends the run with a traceback and a non-zero exit."""
    ctx: dict = {"t_start": time.perf_counter()}
    for phase in phases:
        say(f"--- {phase.__name__.removeprefix('phase_')}")
        phase(ctx)
    return ctx


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the tp=4 bf16 path and its off-mesh comparison")
    args = ap.parse_args(argv)
    ctx = run(FOUR_CHIP_PHASES if args.four_chips else ONE_CHIP_PHASES)
    print(json.dumps({"ok": True, "device": ctx["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
