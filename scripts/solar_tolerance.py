#!/usr/bin/env python3
"""The two readings of `SERVED_TOL_REL` in a hybrid configuration's reference
module (benchmark/references/solar_open2.py, olmo_hybrid.py), through the
comparison run.py makes (`benchmark.correctness.hold_to_reference`).

One engine is booted as the configuration's file says. For each seed the
harness's reference request (`reference_request`: a prompt of that seed, greedy)
is served, and the served tokens are held

- to the reference as it is: the program's reading, which must come out correct;
- to the reference computed under each control (`LOWER` there: int8 weights
  and activations, float8, a bfloat16 state; the module's `CONTROLS` where it
  names its own, as olmo_hybrid.py does for a lost state): a control that
  still comes out correct is something the comparison cannot tell from what
  the file states.

    chiprun -- python3 scripts/solar_tolerance.py --seeds 96 --controls 24
    chiprun -- python3 scripts/solar_tolerance.py --config olmo-hybrid-7b-d20-bf16 --seeds 32 --controls 8
    python3 scripts/solar_tolerance.py --config tiny --seeds 2 --controls 1   # CPU smoke
    python3 scripts/solar_tolerance.py --config olmo-hybrid-7b-d20-bf16 --model tiny-olmo-hybrid ...

`--prompt-bytes` above the engine's chunk (512 tokens) sends the prompt through
the bucketed CHUNK program (`hybrid_prefill_chunk_batch`: the state carried from
chunk to chunk through the pool), which the harness's own request, an admit
program's, never reaches; the summary's `admit_by_shape` says which programs ran.

`--ride` serves each request to an engine with most of its rows decoding
(`keep_rows_busy`), so that the prompt RIDES a full decode round's first step
(`hybrid_mixed_step`) and takes no admit program: the benchmark's own `correct`
request meets an idle engine and never does. The summary's `admit` block says how
many prompts rode and how many took a program of their own.

Prints one JSON line a seed and a summary; writes both to chiprun_out/.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROLS = ("int8", "fp8", "state_bf16")


def keep_rows_busy(gen, rows: int, stop) -> dict:
    """Keep `rows` greedy requests of short prompts decoding until `stop` is
    set, a thread each: three quarters of the slots are more than half, so
    every round is the full batch, and one that finds a request queued and a
    slot free carries the prompt. Returns the requests by id (the tap's filter)."""
    import threading

    from benchmark import trafficgen
    from llm_mcp_tpu.executor.engine import GenRequest

    mine: dict[int, GenRequest] = {}

    def loop(i: int) -> None:
        n = 0
        while not stop.is_set():
            req = GenRequest(
                prompt_ids=gen.tokenizer.encode(trafficgen.text(64, 77000 + i, f"busy{n}")),
                max_tokens=gen.max_seq_len - 160, temperature=0.0)
            mine[id(req)] = req
            gen.submit(req)
            while isinstance(req.out.get(), dict):  # to the stream's end marker
                if stop.is_set():
                    req.cancelled = True
            n += 1

    for i in range(rows):
        threading.Thread(target=loop, args=(i,), daemon=True).start()
    return mine


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="solar-open2-250b-ep8-bf16",
                    help="a file of benchmark/configs, or `tiny` for tiny-solar on the CPU")
    ap.add_argument("--model", default="",
                    help="boot this TPU_MODEL with 4 slots instead of the file's (a CPU smoke)")
    ap.add_argument("--seeds", type=int, default=96)
    ap.add_argument("--controls", type=int, default=24, help="seeds each control is read on")
    ap.add_argument("--first-seed", type=int, default=3200006000)
    ap.add_argument("--prompt-bytes", type=int, default=0,
                    help="the prompt's bytes instead of the configuration's reference_request")
    ap.add_argument("--tokens", type=int, default=0,
                    help="the tokens served instead of the configuration's reference_request")
    ap.add_argument("--only", default="", help="of the module's controls, these alone (comma-separated)")
    ap.add_argument("--ride", action="store_true",
                    help="serve beside decoding rows, so that the prompt rides a decode round")
    args = ap.parse_args()

    from benchmark import correctness, run as bench_run, trafficgen

    if args.config == "tiny":
        args.config, args.model = "solar-open2-250b-ep8-bf16", "tiny-solar"
    config = json.load(open(os.path.join(ROOT, "benchmark", "configs", args.config + ".json")))
    env = {k: str(v) for k, v in config["program"]["env"].items()}
    if args.model:
        env.update(TPU_MODEL=args.model, TPU_MAX_SLOTS="4")
    os.environ.update(env)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.utils import config as ucfg
    from llm_mcp_tpu.utils.tokens import messages_to_prompt

    ucfg.enable_compile_cache()
    name, module = bench_run.load_reference(config)
    controls = tuple(getattr(module, "CONTROLS", CONTROLS))
    if args.only:
        controls = tuple(c for c in controls if c in args.only.split(","))
    gen = GenerationEngine(
        env["TPU_MODEL"], max_slots=int(env["TPU_MAX_SLOTS"]), max_seq_len=int(env["TPU_MAX_SEQ_LEN"]),
        dtype=jnp.bfloat16, kv_quant=env["TPU_KV_QUANT"], seed=int(config.get("weights_seed", 0)),
        **({"prefill_chunk": int(env["TPU_PREFILL_CHUNK"])} if "TPU_PREFILL_CHUNK" in env else {}),
    ).start()
    asked = {k: v for k, v in (("prompt_bytes", args.prompt_bytes), ("tokens", args.tokens)) if v}
    config = dict(config, reference_request=dict(config.get("reference_request", {}), **asked))
    n_bytes, n_tokens = correctness.reference_request(config, gen.max_seq_len)
    mask = gen._allowed_mask
    allowed = np.arange(gen.cfg.vocab_size) if mask is None else np.flatnonzero(np.asarray(mask))

    busy: dict = {}
    if args.ride:
        import threading

        stop = threading.Event()
        rows = gen.max_slots * 3 // 4
        busy = keep_rows_busy(gen, rows, stop)
        while sum(s is not None for s in gen._slots) < rows:
            time.sleep(0.05)

    def serve(seed: int) -> tuple[list[int], list[int]]:
        got: dict = {}
        emit = gen._process_token

        def tap(slot, tok, pos):
            if id(slot.req) not in busy:
                got.setdefault("ids", list(slot.req.prompt_ids))
                got.setdefault("out", []).append(int(tok))
            return emit(slot, tok, pos)

        gen._process_token = tap
        try:
            # the prompt as `/v1/chat/completions` renders the harness's one user message
            gen.generate(messages_to_prompt([{"role": "user", "content": trafficgen.text(n_bytes, seed, "ref")}]),
                         max_tokens=n_tokens, temperature=0.0)
        finally:
            del gen._process_token
        return got["ids"], got["out"]

    def held(ids, out) -> tuple[float, str, int]:
        """(worst regret, "", tokens over the limit) where the comparison
        passes; where it refuses, its message and the worst regret by the same
        formula over all tokens (the comparison stops at the first token over
        the limit). The reference's rows are computed once and handed to the
        comparison as they are."""
        kept = {}

        def once(*a):
            t0 = time.monotonic()
            kept["ref"] = module.logits(*a)
            spent.append(time.monotonic() - t0)
            return kept["ref"]

        shim = types.SimpleNamespace(SERVED_TOL_REL=module.SERVED_TOL_REL, logits=once)
        try:
            why = ""
            correctness.hold_to_reference(shim, gen, ids, out)
        except AssertionError as e:
            why = str(e)
        rel = [float((np.max(r) - r[np.flatnonzero(allowed == t)[0]]) / np.max(np.abs(r)))
               for r, t in zip(kept["ref"], out)]
        return max(rel), why, sum(x > module.SERVED_TOL_REL for x in rel)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    lines, spent = [], []  # spent: seconds of each call of the reference's logits
    served = [serve(args.first_seed + i) for i in range(args.seeds)]
    admit = gen.perf_stats()["admit"]
    if args.ride:
        stop.set()
    for i, (ids, out) in enumerate(served):
        value, why, over = held(ids, out)
        lines.append({"seed": args.first_seed + i, "prompt_tokens": len(ids), "served": len(out),
                      "distinct": len(set(out)), "program": value, "program_refused": why})
        print(json.dumps(lines[-1]), flush=True)
    for lower in controls:
        module.LOWER = lower
        jax.clear_caches()
        for i, (ids, out) in enumerate(served[: args.controls]):
            value, why, over = held(ids, out)
            lines[i][lower], lines[i][lower + "_refused"] = value, why
            print(json.dumps({"seed": lines[i]["seed"], lower: value, "tokens_over": over,
                              "refused": why}), flush=True)
    module.LOWER = None
    jax.clear_caches()
    gen.shutdown()

    def summary(key: str) -> dict:
        rows = [r for r in lines if key in r]
        read = sorted(r[key] for r in rows)
        return {"seeds": len(rows), "not_correct": sum(bool(r[key + "_refused"]) for r in rows),
                "min": read[0], "median": statistics.median(read), "max": read[-1]}

    result = {"tolerance": float(module.SERVED_TOL_REL), "reference": name,
              "request": {"prompt_bytes": n_bytes, "tokens": n_tokens},
              "reference_s": {"first": spent[0], "median": statistics.median(spent)},
              "admit_by_shape": dict(admit["by_shape"]),
              "admit": {"rides": admit["rides"], "own_prompts": admit["own_prompts"]},
              **{key: summary(key) for key in ("program", *controls)}}
    print("SUMMARY", json.dumps(result), flush=True)
    with open(os.path.join(ROOT, "chiprun_out", f"{name}_tolerance_{n_bytes}_{n_tokens}.json"), "w") as f:
        json.dump({"summary": result, "seeds": lines}, f)
    return 0 if result["program"]["not_correct"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
