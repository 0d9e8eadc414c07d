#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it refuses to run without a TPU, boots the cell's engine and
CoreServer the way `python -m llm_mcp_tpu.api` does (every default of the
program left alone), starts the load generator as a child process that never
imports JAX and talks only HTTP to 127.0.0.1, warms up with the cell's own
traffic under another seed, measures for `--seconds`, checks what was served
against the benchmark's own float32 reference, and prints one JSON object as
the last line of standard output. Any phase that raises ends the run with a
traceback, a non-zero exit code and no result line.

With `--trace 0` the line holds the cell's end-to-end metrics; with `--trace 1`
its per-layer metrics, taken from counters read at the window's edges and from
a profiler trace of a few seconds in the middle of the window; a reader that
sets a count beside the trace's device times takes the count between the
slice's own edges (`counters.slice_of`).

Everything that belongs to one cell is data: BENCHMARK.json names the cell's
configuration (`benchmark/configs/<config>.json`) and traffic mix
(`benchmark/traffic/<traffic>.json`), and every metric is a reader of its own
(`benchmark/end_to_end/<name>.py`, `benchmark/layer_metrics/<name>.py`).
"""

from __future__ import annotations

import argparse
import collections
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading
import time

T_IMPORT = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trafficgen  # noqa: E402  (pure standard library)

WARM_SEED = 7_777_777  # warm-up traffic never shares a seed with a window
# Seconds of the window a traced run profiles. 8 and not 3 (PR 47): where most
# rounds carry a prompt (the recurrent cells since PR 42) one round in ten to
# fifteen is the PLAIN program the round readers time, and a slice of 3 s held
# 21-29 rounds of 100-135 ms: two plain ones, or none, and then no reading.
TRACE_SLICE_S = 8.0
ROUND_EVENTS = ("decode", "fused", "fused_rag", "mixed")  # the flight ring's one event a round
ROUND_LEAD_S = 0.3  # a round runs on the device up to two rounds after the host dispatched it


def say(msg: str) -> None:
    print(msg, flush=True)


def process_start() -> float:
    """CLOCK_MONOTONIC reading at which this process started, from /proc (the
    interpreter's own start-up is set-up too); the first import otherwise."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        boot_to_start = ticks / os.sysconf("SC_CLK_TCK")
        since_boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        return time.monotonic() - (since_boot - boot_to_start)
    except (OSError, ValueError, IndexError, AttributeError):
        return T_IMPORT


# -- what the cell is: BENCHMARK.json and the files it names ------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The cell's entry, its configuration and traffic files, and the metric
    entries that apply to it."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": load_json(os.path.join(root, config_entry["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_reader(kind: str, name: str):
    """The module benchmark/<kind>/<name>.py, found by the metric's name."""
    path = os.path.join(HERE, kind, name + ".py")
    module = f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REFERENCE_NEEDS = {"generation": ("logits", "SERVED_TOL_REL"),
                   "embedding": ("pooled", "EMBED_TOL_COS")}
TABLES = ("HELD", "ONLY", "STATED")  # what a reference module may bring beside its forward


def load_reference(config: dict):
    """(name, module) of the plain reference the configuration's file names:
    benchmark/references/<name>.py, or benchmark/reference.py without the key.
    It must hold what the configuration's engine kind is compared through, and
    may hold tables for `check_sizes` (the contract is at the top of
    correctness.py): a path that run.py's own tables hold, or two of the
    module's, and a `STATED` path with no reason, are refused here. One overlap
    stands: a path of `ONLY_VALUE` in the module's `HELD`, and only there. A
    family that brings the behaviour (routing in groups, a layer pattern) holds
    the key to what ITS program computes with, and `check_sizes` then compares
    the file with that holder."""
    name = config.get("reference")
    if name is None:
        from benchmark import reference as mod

        name = "reference"
    else:
        mod = load_reader("references", name)
    lacks = [a for a in ("check", *REFERENCE_NEEDS[config["program"]["engine"]])
             if not hasattr(mod, a)]
    if lacks:
        raise AttributeError(f"reference {name!r} lacks {lacks}")
    seen = own_paths()
    for table in TABLES:
        paths = set(getattr(mod, table, {}))
        twice = sorted(seen & (paths - set(ONLY_VALUE) if table == "HELD" else paths))
        if twice:
            raise AssertionError(
                f"reference {name!r}: {table} holds {twice}, which another table holds already "
                f"(run.py's, or one of the module's): a path is held once, and only `HELD` may "
                f"take over a path of run.py's `ONLY_VALUE`")
        seen |= paths
    unsaid = sorted(p for p, why in getattr(mod, "STATED", {}).items() if not str(why).strip())
    if unsaid:
        raise AssertionError(f"reference {name!r}: STATED gives no reason for {unsaid}")
    return name, mod


# -- the device ----------------------------------------------------------------


def require_tpu(chips: int) -> dict:
    """Fail at once unless JAX's devices are TPUs and enough of them. First
    touch of JAX; the load generator's child never gets here."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"benchmark: needs {chips} TPU chip(s); JAX reports "
            f"platform={devs[0].platform!r} x{len(devs)}. No CPU leg.")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


class CompileEvents:
    """Times of JAX's own compilation-cache events (chip_smoke.py's listener).
    `compile_requests_use_cache` fires for every executable this process
    builds, `cache_hits` for those it loaded from the persistent cache instead
    of compiling. Either inside the window is a stall."""

    def __init__(self) -> None:
        import jax

        self.requests: list[float] = []
        self.hits: list[float] = []
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests.append(time.monotonic())
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.monotonic())

    def count(self) -> int:
        return len(self.requests)

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.requests if a <= t < b)


# -- the system under test -----------------------------------------------------

MODEL_KEYS = {  # the published config's key -> the program's ModelConfig field
    "hidden_size": "dim", "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "intermediate_size": "ffn_hidden", "head_dim": "resolved_head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "sliding_window": "sliding_window", "attention_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings", "hidden_act": "act",
    # latent attention
    "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    # routed experts
    "n_routed_experts": "n_experts", "num_experts_per_tok": "experts_per_tok",
    "n_shared_experts": "n_shared_experts", "moe_intermediate_size": "moe_ffn_hidden",
    "first_k_dense_replace": "first_dense_layers",
    "routed_scaling_factor": "routed_scaling_factor", "norm_topk_prob": "norm_topk_prob",
}
DERIVED_KEYS = {  # a key held to something the program's table gives in another form
    # latent attention expands a K and a V for every head: the published configs
    # state their head count there, the program's table the latent cache's one row
    "num_key_value_heads": lambda c: c.n_heads if c.kv_lora_rank else c.n_kv_heads,
    "embedding_width": lambda c: c.embed_dim or c.dim,
    "pooling": lambda c: {"last": "last_token"}.get(c.pooling, c.pooling),
}
# A key the program has one behaviour for: any other value is refused, unless the
# configuration's reference module holds the key itself (`HELD`), as a family
# that brings the behaviour does.
ONLY_VALUE = {
    "moe_layer_freq": 1,  # every layer after the dense ones is routed
    "n_group": 1, "topk_group": 1,  # greedy top-k over all experts, no groups
}
ROPE_KEYS = {  # inside the nested "rope_scaling" group (null: no scaling, factor 1)
    "factor": "rope_factor", "original_max_position_embeddings": "rope_orig_max",
    "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow",
    "mscale": "yarn_mscale", "mscale_all_dim": "yarn_mscale_all_dim",
    "low_freq_factor": "llama3_low_freq_factor", "high_freq_factor": "llama3_high_freq_factor",
    "type": "rope_type",
}
# Keys a file states that are not sizes of the model the program builds: the
# positions the published model declares (the file's `program.env` says how many
# are served), the released code's class and family names, and the type of the
# checkpoint (boot() serves bfloat16).
STATED_NOT_HELD = {"max_position_embeddings", "architectures", "model_type", "torch_dtype"}
# The harness's own keys of a configuration's file. Every other key is the
# model's, and check_sizes walks it.
HARNESS_KEYS = ("name", "source", "reference", "reference_request", "reduced", "assumed",
                "deployment", "weights_seed", "program", "published")


def own_paths() -> set[str]:
    """Every path run.py's own tables hold. A reference module may hold none of
    them again, but for a path of `ONLY_VALUE` in its `HELD` (`load_reference`)."""
    return ({*MODEL_KEYS, *DERIVED_KEYS, *ONLY_VALUE, *STATED_NOT_HELD}
            | {f"rope_scaling.{k}" for k in ROPE_KEYS})


def walk(group: dict, prefix: str = "") -> dict:
    """{dotted path: value} of every leaf of a group: a nested group is walked,
    a list is one value, compared whole."""
    out: dict = {}
    for key, value in group.items():
        if isinstance(value, dict):
            out.update(walk(value, f"{prefix}{key}."))
        else:
            out[prefix + key] = value
    return out


def model_paths(config: dict) -> dict:
    """{path: value} of the model's keys of a configuration's file. A
    `rope_scaling` that is null, or gives no factor, scales by 1."""
    model = {k: v for k, v in config.items() if k not in HARNESS_KEYS}
    if "rope_scaling" in model:
        model["rope_scaling"] = {"factor": 1.0, **(model["rope_scaling"] or {})}
    return walk(model)


def same(stated, held) -> bool:
    """A list or a string is equal or not; a number, a bool and null are
    compared as numbers, null reading as 0."""
    if isinstance(held, tuple):
        held = list(held)
    if any(isinstance(v, (list, str)) for v in (stated, held)):
        return type(stated) is type(held) and stated == held
    return float(stated or 0) == float(held or 0)


def check_published(config: dict) -> dict:
    """`published` states the source's value of every path in `reduced`, and of
    nothing else; a file that cuts nothing has no such group. Returns its paths."""
    reduced, published = config.get("reduced", []), config.get("published")
    if not reduced and published is None:
        return {}
    paths = walk({"published": published or {}})

    def states(q: str, p: str) -> bool:  # p itself, or a path inside the group p
        return q == f"published.{p}" or q.startswith(f"published.{p}.")

    lacks = [p for p in reduced if not any(states(q, p) for q in paths)]
    extra = [q for q in paths if not any(states(q, p) for p in reduced)]
    if lacks or extra:
        raise AssertionError(
            f"{config['name']}: `published` holds the source's value of each path in `reduced` "
            f"and of no other: it lacks {sorted(lacks)} and holds {sorted(extra)} beside them")
    return paths


def check_sizes(config: dict, model_cfg, module=None) -> list[str]:
    """The configuration's file is what is run: every model key it states, at
    any depth (number, bool, string or list), must be what the program's own
    table gives the engine (null reads as 0). run.py's tables come first, then
    `HELD`, `ONLY` and `STATED` of the configuration's reference module. Where
    the module's `HELD` names a path of `ONLY_VALUE`, the file is compared with
    the module's holder, a list element by element; where it does not,
    `ONLY_VALUE` stands. A path that no table knows is an error, not a default:
    a width nobody compares could be cut and still boot. Returns the paths the
    file states that are held to nothing, each with the reason where the module
    gives one."""
    held = {k: getattr(model_cfg, f) for k, f in MODEL_KEYS.items()}
    held.update({k: f(model_cfg) for k, f in DERIVED_KEYS.items()}, **ONLY_VALUE)
    held.update({f"rope_scaling.{k}": getattr(model_cfg, f) for k, f in ROPE_KEYS.items()})
    held.update({k: f(model_cfg) for k, f in getattr(module, "HELD", {}).items()})
    held.update(getattr(module, "ONLY", {}))
    why = {**dict.fromkeys(STATED_NOT_HELD, ""), **getattr(module, "STATED", {})}
    # a path of `published` is compared where the module holds it, and only there
    stated = {**model_paths(config),
              **{p: v for p, v in check_published(config).items() if p in held or p in why}}
    unknown = sorted(set(stated) - set(held) - set(why))
    if unknown:
        raise AssertionError(
            f"{config['name']}: the file states {unknown}, which check_sizes compares with "
            f"nothing of the program: a size nobody holds is an error")
    for key in sorted(stated.keys() & held.keys()):
        if not same(stated[key], held[key]):
            raise AssertionError(
                f"{config['name']}: {key}={stated[key]} in the file, "
                f"{held[key]} in the program ({model_cfg.name})")
    return [f"{p} ({why[p]})" if why[p] else p for p in sorted(stated.keys() & why.keys())]


def check_before_boot(config: dict, cfg):
    """What can be refused before the engine is built, in seconds: a reference
    module that is not there or brings a table it may not, a key in the file
    that is not the program's or that nothing holds, a configuration its
    reference module does not cover, a reference request that does not fit.
    Returns the reference."""
    from benchmark import correctness
    from llm_mcp_tpu.models.configs import resolve_config

    if config["program"]["engine"] == "generation":
        model_cfg = resolve_config(cfg.tpu_model, cfg.tpu_weights_dir)
        correctness.reference_request(config, cfg.tpu_max_seq_len)
    else:
        model_cfg = resolve_config(cfg.tpu_embed_model, cfg.tpu_embed_weights_dir)
    name, module = load_reference(config)
    unheld = check_sizes(config, model_cfg, module)
    module.check(model_cfg)
    say(f"reference: {name}; sizes in the file are the program's ({model_cfg.name})")
    say(f"stated in the file and held to nothing: {'; '.join(unheld) or 'nothing'}")
    return name, module


def boot(config: dict) -> dict:
    """Engine + CoreServer, the way api/__main__.py builds them, from the
    environment the configuration's file states."""
    prog = config["program"]
    os.environ.update({k: str(v) for k, v in prog["env"].items()})
    import jax.numpy as jnp

    from llm_mcp_tpu.api.server import CoreServer
    from llm_mcp_tpu.executor import EmbeddingEngine, GenerationEngine
    from llm_mcp_tpu.state.db import Database
    from llm_mcp_tpu.utils.config import Config

    cfg = Config()
    reference = check_before_boot(config, cfg)
    t0 = time.monotonic()
    gen = emb = None
    if prog["engine"] == "generation":
        gen = GenerationEngine(
            cfg.tpu_model, max_slots=cfg.tpu_max_slots, max_seq_len=cfg.tpu_max_seq_len,
            dtype=jnp.bfloat16, weights_dir=cfg.tpu_weights_dir, quant=cfg.tpu_quant,
            kv_quant=cfg.tpu_kv_quant, prefill_chunk=cfg.tpu_prefill_chunk,
            decode_compact=cfg.tpu_decode_compact, prompt_cache_mb=cfg.tpu_prompt_cache_mb,
            prefill_buckets=cfg.tpu_prefill_buckets, target_ttft_ms=cfg.tpu_target_ttft_ms,
            seed=int(config.get("weights_seed", 0)),
        ).start()
        engine, model = gen, cfg.tpu_model
    elif prog["engine"] == "embedding":
        emb = EmbeddingEngine(
            cfg.tpu_embed_model, max_seq_len=min(cfg.tpu_max_seq_len, 8192),
            dtype=jnp.bfloat16, weights_dir=cfg.tpu_embed_weights_dir,
            quant=cfg.tpu_embed_quant, seed=int(config.get("weights_seed", 0)),
        )
        engine, model = emb, cfg.tpu_embed_model
    else:
        raise ValueError(f"unknown engine kind {prog['engine']!r}")
    check_sizes(config, engine.cfg, reference[1])
    t1 = time.monotonic()
    srv = CoreServer(
        cfg, db=Database(":memory:"),
        gen_engines={model: gen} if gen else {},
        embed_engines={model: emb} if emb else {},
    ).start("127.0.0.1", 0)
    say(f"boot: engine {t1 - t0:.1f} s, server and critical warm-up {time.monotonic() - t1:.1f} s")
    return {"gen": gen, "emb": emb, "engine": engine, "srv": srv, "port": srv.api.port,
            "model": model, "reference": reference}


def snapshot(sut: dict, compiles: CompileEvents) -> dict:
    """Every counter a layer metric may read, at one moment."""
    snap: dict = {"t": time.monotonic(), "compile_events": compiles.count()}
    gen = sut["gen"]
    if gen is not None:
        from llm_mcp_tpu.kernels import attention as A

        snap.update(
            perf=gen.perf_stats(), scheduler=gen.scheduler_stats(),
            waterfall=gen.waterfall_stats(), ledger=gen._ledger.stats(),
            ledger_keys=sorted(f"{r['phase']} {r['key']}" for r in gen._ledger.table()),
            reference_falls=dict(A.reference_falls),
        )
    return snap


class EmbedTap:
    """Host clock around the embedding engine's forward and fetch, wrapped
    from the benchmark's side (the program has no counter there yet). Only a
    traced run installs it."""

    def __init__(self, emb) -> None:
        self.calls: list[tuple[float, float, int, int]] = []  # start, end, padded, true tokens
        inner = emb._fwd

        def fwd(params, tokens, lengths):
            import jax

            t0 = time.monotonic()
            out = jax.block_until_ready(inner(params, tokens, lengths))
            self.calls.append((t0, time.monotonic(), int(tokens.size), int(lengths.sum())))
            return out

        emb._fwd = fwd


# -- the load generator ----------------------------------------------------------


def run_loadgen(work_dir: str, plan: dict, tag: str) -> subprocess.Popen:
    plan_path = os.path.join(work_dir, f"{tag}.plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), plan_path,
         os.path.join(work_dir, f"{tag}.records.json")],
        env=env, stdin=subprocess.DEVNULL)


def finish_loadgen(proc: subprocess.Popen, work_dir: str, tag: str, timeout: float) -> list[dict]:
    try:
        rc = proc.wait(timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RuntimeError(f"load generator ({tag}) did not end in {timeout:.0f} s")
    if rc != 0:
        raise RuntimeError(f"load generator ({tag}) exited with {rc}")
    return load_json(os.path.join(work_dir, f"{tag}.records.json"))


def warm_up(sut: dict, spec: dict, work_dir: str, compiles: CompileEvents) -> None:
    """The cell's own traffic under another seed, round after round, until a
    round builds no executable (the first rounds may reshape the mix, as the
    traffic file's `warmup_rounds` says); then the wait for the program's
    warm-up zoo (`fully_warm`), so that no background compile runs inside the
    window. The zoo compiles in the background while the rounds run."""
    from benchmark import reduce

    base = spec["traffic"]
    shaped = base.get("warmup_rounds", [])
    gen = sut["gen"]
    for k in range(int(base.get("warmup_rounds_max", 4))):
        traffic = dict(base, **(shaped[k] if k < len(shaped) else {}))
        before = compiles.count()
        seen = {(r["phase"], r["key"]) for r in gen._ledger.table()} if gen is not None else set()
        plan = trafficgen.make_plan(traffic, WARM_SEED + k, float(traffic["warmup_s"]),
                                    model=sut["model"], preroll_s=0.0, salt=f"w{k}x")
        plan.update(port=sut["port"], t_start=time.monotonic() + 0.2,
                    stop_s=float(traffic["warmup_s"]), timeout_s=600.0)
        t0 = time.monotonic()
        recs = finish_loadgen(run_loadgen(work_dir, plan, f"warm{k}"), work_dir, f"warm{k}", 900.0)
        bad = [r for r in recs if not reduce.ok(r)]
        added = compiles.count() - before
        new = [f"{r['phase']} {r['key']} {r['total_s']:.1f}s" for r in gen._ledger.table()
               if (r["phase"], r["key"]) not in seen] if gen is not None else []
        say(f"warm-up round {k}: {len(recs)} requests ({len(bad)} failed) in "
            f"{time.monotonic() - t0:.1f} s, {added} executables compiled or loaded"
            + (f"; first dispatched: {', '.join(new)}" if new else ""))
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0]}")
        if added == 0 and k >= len(shaped):
            break
    if gen is not None:
        t0 = time.monotonic()
        while gen.warmup_stats().get("state") != "fully_warm":
            if time.monotonic() - t0 > 900.0:
                raise RuntimeError(f"warm-up zoo not warm after 900 s: {gen.warmup_stats()}")
            time.sleep(0.25)
        say(f"warm-up zoo fully_warm after {time.monotonic() - t0:.1f} s more")


def slice_edge(sut: dict) -> dict:
    """The counters a `device_trace` reader sets beside the slice's device
    times (`counters.slice_of`), at one moment: the perf observatory's phases
    and the expert layer's counts, both in `perf_stats()`."""
    gen = sut["gen"]
    return {"t": time.monotonic(), **({"perf": gen.perf_stats()} if gen is not None else {})}


def ring_rounds(sut: dict, a: float, b: float) -> list[tuple[str, int, float]]:
    """(kind, decode rows, t) of every round the engine dispatched in [a, b),
    from the flight ring's one event a round: EVERY round, where the perf
    observatory samples one dispatch in 32, which in a slice is one or none."""
    gen = sut["gen"]
    out = []
    for ev in gen._flight.snapshot() if gen is not None else ():
        f = ev["fields"] or {}
        if ev["etype"] in ROUND_EVENTS and a <= f.get("t", -1.0) < b:
            out.append((ev["etype"], int(f["rows"]), float(f["t"])))
    return out


def trace_slice(trace_dir: str, at: float, out: dict, sut: dict) -> None:
    """Profile TRACE_SLICE_S seconds starting at monotonic time `at`, and read
    the counters at the slice's two edges and the rounds the engine dispatched
    between them (`out["cut"]`: start, end, rounds), so that what a reader
    divides by the slice's device time is counted over the slice's own rounds.
    No Python tracer: it slows the host it shares with the server."""
    import jax

    time.sleep(max(0.0, at - time.monotonic()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    out["start"] = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        first = slice_edge(sut)
        time.sleep(max(0.0, out["start"] + TRACE_SLICE_S - time.monotonic()))
        last = slice_edge(sut)
        out["cut"] = {"start": first, "end": last,
                      "rounds": ring_rounds(sut, first["t"] - ROUND_LEAD_S, last["t"])}
    finally:
        out["stop"] = time.monotonic()
        jax.profiler.stop_trace()
        out["written"] = time.monotonic()


def measure(sut: dict, spec: dict, args, work_dir: str, compiles: CompileEvents) -> dict:
    """One plan: pre-roll, the window, the drain. Counters are read at the
    window's edges, waterfall rows polled while it runs."""
    traffic = spec["traffic"]
    plan = trafficgen.make_plan(traffic, args.seed, float(args.seconds), model=sut["model"])
    pre, seconds = plan["preroll_s"], plan["seconds"]
    t_start = time.monotonic() + 0.3
    plan.update(port=sut["port"], t_start=t_start, stop_s=pre + seconds,
                timeout_s=float(traffic.get("request_timeout_s", 120.0)))
    proc = run_loadgen(work_dir, plan, "window")
    w0, w1 = t_start + pre, t_start + pre + seconds
    trace, tracer = {}, None
    if args.trace:
        trace["dir"] = os.path.join(work_dir, "trace")
        tracer = threading.Thread(
            target=trace_slice, args=(trace["dir"], (w0 + w1) / 2 - TRACE_SLICE_S / 2, trace, sut))
        tracer.start()
    time.sleep(max(0.0, w0 - time.monotonic()))
    start = snapshot(sut, compiles)
    rows: dict[str, dict] = {}
    gen = sut["gen"]
    while time.monotonic() < w1:
        if gen is not None and args.trace:
            for row in gen.waterfall_recent(128):
                rows[row["trace"] or row["rid"]] = row
        time.sleep(min(0.5, max(0.0, w1 - time.monotonic())))
    end = snapshot(sut, compiles)
    records = finish_loadgen(proc, work_dir, "window", float(plan["timeout_s"]) + 60.0)
    if tracer is not None:
        tracer.join()
    if gen is not None and args.trace:
        for row in gen.waterfall_recent(128):
            rows[row["trace"] or row["rid"]] = row
    run = {"records": records, "window": (pre, pre + seconds), "window_abs": (w0, w1),
           "t_start": t_start, "start": start, "end": end, "waterfall_rows": rows,
           "trace": trace, "plan": plan,
           "window_compiles": compiles.between(w0, w1)}
    cut = trace.pop("cut", None)
    if cut:  # the traced slice as a window of its own: counters.slice_of
        a, b = cut["start"]["t"], cut["end"]["t"]
        run["slice"] = dict(cut, window_abs=(a, b), window=(a - t_start, b - t_start))
    return run


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_proc = process_start()

    spec = load_cell(ROOT, args.workload)
    device = require_tpu(int(spec["cell"]["chips"]))
    say(f"[{device['kind']} x{device['count']}] workload={args.workload} "
        f"config={spec['cell']['config']} seed={args.seed} seconds={args.seconds} trace={args.trace}")

    from llm_mcp_tpu.utils import config as ucfg

    cache_dir = ucfg.enable_compile_cache()
    say(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(cache_dir) else 0} entries)")
    compiles = CompileEvents()

    sut = boot(spec["config"])
    work_dir = os.path.join(ROOT, ".bench_work", f"{args.workload}.{os.getpid()}")
    os.makedirs(work_dir, exist_ok=True)
    try:
        tap = EmbedTap(sut["emb"]) if args.trace and sut["emb"] is not None else None
        warm_up(sut, spec, work_dir, compiles)
        run = measure(sut, spec, args, work_dir, compiles)
        run.update(sut=sut, spec=spec, device=device, args=args, embed_tap=tap,
                   setup_s=run["window_abs"][0] - t_proc,
                   miss_ms=float(run["plan"]["timeout_s"]) * 1e3)
        from benchmark import correctness, counters, reduce, trace_reduce

        bad = [r for r in run["records"] if not reduce.ok(r)]
        checks = correctness.check(run)
        first = sorted(set(run["end"].get("ledger_keys", [])) - set(run["start"].get("ledger_keys", [])))
        say(f"window: {len(run['records'])} requests sent, {len(bad)} failed, "
            f"{run['window_compiles']} executables compiled or loaded inside it"
            + (f"; first dispatched inside it: {', '.join(first)}" if first else ""))

        reduced = None
        if args.trace:
            path = trace_reduce.find_xplane(run["trace"]["dir"])
            t0 = time.monotonic()
            reduced = trace_reduce.reduce_trace(path) if path else None
            if reduced is None:
                raise RuntimeError("traced run: the trace holds no device operation")
            cut = run.get("slice", {})
            kinds = collections.Counter(kind for kind, _rows, _t in cut.get("rounds", ()))
            say(f"traced slice: {reduced['window_s']:.3f} s on the device, read in "
                f"{time.monotonic() - t0:.1f} s; rounds dispatched in it by kind: {dict(kinds)}, rows of "
                f"the plain ones {counters.plain_rows(cut)}; whole runs [count, mean s]: "
                f"{json.dumps(reduced['whole_runs'])}")
            keep = os.environ.get("BENCH_KEEP_TRACE", "")
            if keep:  # the builder's own look at a raw trace; the driver never sets it
                os.makedirs(keep, exist_ok=True)
                shutil.copy(path, os.path.join(keep, f"{args.workload}.xplane.pb"))
        run["trace_reduced"] = reduced

        metrics: dict = {}
        kind = "layer_metrics" if args.trace else "end_to_end"
        for entry in spec["per_layer" if args.trace else "end_to_end"]:
            value = load_reader(kind, entry["name"]).read(run)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
        import jax

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())
        device["memory_peak_bytes"] = int(peak)
        result = {"correct": bool(checks["correct"]), "attempted": len(run["records"]),
                  "failed": len(bad), "metrics": metrics, "device": device,
                  "checks": checks["notes"], "window_compiles": run["window_compiles"]}
        if reduced is not None:
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            say("step programs by device time: " + json.dumps(reduced["modules"]))
    finally:
        sut["srv"].shutdown()
        shutil.rmtree(work_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
