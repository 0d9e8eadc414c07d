#!/usr/bin/env python3
"""LOGITS of the step programs against the plain reference, at the widths and
cache shapes a configuration's file states, under each of the reference
module's controls: what `correct` (sixteen served greedy tokens) cannot tell
from the program in the window-and-global configuration (a lost ring position,
rope on the global layer, the gates' factor left out: PERF.md section 6, PR 43).

One engine is built as the file says and NOT started. For each seed a prompt of
`--prompt-tokens` allowed ids goes through the engine's own admit program
(`engine._admit_fn`: whole-prompt prefill, int8 rows and rings inserted at the
slot, which the seed before left its leftovers in), then `--steps` decode steps
of the full batch with that one row live (`llama_decode_step`, the body of a
decode round: both attention arms and the append kernels), teacher-forced with
seeded tokens, so the rings wrap `steps / ring` times during decode. The logits
of every step, cut to the ids the engine may emit, are held to the reference's
on the same sequence, a row's largest difference as a share of the reference
row's largest |logit|; one reading a control = the MEDIAN over the rows (a
router's choice moved by rounding changes single rows by a whole gated expert:
the median does not see them).

The hold: the program's reading lies under `--limit` and every control's over
it. Exit 0 where it does. Prints one JSON line a seed and a summary; writes
both to chiprun_out/.

    chiprun -- python3 scripts/logit_hold.py --seeds 2 --steps 384
    LLM_MCP_TPU_ATTN=pallas python3 scripts/logit_hold.py --model tiny-kexaone \\
        --prompt-tokens 180 --steps 140 --seeds 1   # CPU smoke, kernels interpreted
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="k-exaone-236b-ep8-bf16", help="a file of benchmark/configs")
    ap.add_argument("--model", default="",
                    help="boot this TPU_MODEL with 4 slots instead of the file's (a CPU smoke)")
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=4300003000)
    ap.add_argument("--prompt-tokens", type=int, default=707)
    ap.add_argument("--steps", type=int, default=384)
    ap.add_argument("--slot", type=int, default=3)
    ap.add_argument("--limit", type=float, default=None,
                    help="the limit on the median (default: the reference module's LOGIT_TOL_REL)")
    args = ap.parse_args()

    from benchmark import correctness, run as bench_run

    config = json.load(open(os.path.join(ROOT, "benchmark", "configs", args.config + ".json")))
    env = {k: str(v) for k, v in config["program"]["env"].items()}
    if args.model:
        env.update(TPU_MODEL=args.model, TPU_MAX_SLOTS="4")
    os.environ.update(env)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_mcp_tpu.executor import GenerationEngine
    from llm_mcp_tpu.models.llama import llama_decode_step
    from llm_mcp_tpu.utils import config as ucfg

    ucfg.enable_compile_cache()
    name, module = bench_run.load_reference(config)
    limit = float(module.LOGIT_TOL_REL if args.limit is None else args.limit)
    gen = GenerationEngine(
        env["TPU_MODEL"], max_slots=int(env["TPU_MAX_SLOTS"]), max_seq_len=int(env["TPU_MAX_SEQ_LEN"]),
        dtype=jnp.bfloat16, kv_quant=env["TPU_KV_QUANT"], seed=int(config.get("weights_seed", 0)),
        **({"prefill_chunk": int(env["TPU_PREFILL_CHUNK"])} if "TPU_PREFILL_CHUNK" in env else {}),
    )  # never started: the step programs are called from here
    cfg, B, S = gen.cfg, gen.max_slots, gen.max_seq_len
    P, N, slot = args.prompt_tokens, args.steps, args.slot
    total = P + N + (-(P + N) % correctness.PAD_TO)
    assert total < S and slot < B, (total, S, slot, B)
    mask = gen._allowed_mask
    allowed = np.arange(cfg.vocab_size) if mask is None else np.flatnonzero(np.asarray(mask))
    bucket = gen._bucket(P)
    step = jax.jit(lambda params, ck, cv, toks, lens: llama_decode_step(
        cfg, params, ck, cv, toks, lens, attn_impl=gen.attn_impl), donate_argnums=(1, 2))
    ck, cv = gen._ck, gen._cv
    sampling = (gen._d_temp, gen._d_topk, gen._d_topp, gen._d_last_tok)

    def program(seq: np.ndarray) -> np.ndarray:
        """Logits [N, allowed] after each of the N tokens that follow the prompt."""
        nonlocal ck, cv, sampling
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :P] = seq[:P]
        ck, cv, *sampling, _ = gen._admit_fn(
            gen.params, ck, cv, *sampling, jnp.asarray(prompt),
            jnp.asarray([slot, P, 0, 1, 0], jnp.int32), jnp.asarray([0.0, 1.0], jnp.float32))
        lens = np.full(B, S, np.int32)  # the other rows are parked
        lens[slot] = P
        out = []
        for t in range(P, P + N):
            toks = np.zeros(B, np.int32)
            toks[slot] = seq[t]
            logits, ck, cv = step(gen.params, ck, cv, jnp.asarray(toks), jnp.asarray(lens))
            out.append(np.asarray(logits[slot], np.float32)[allowed])
            lens[slot] += 1
        return np.stack(out)

    rows = np.arange(P, P + N)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    seqs, got, lines = [], [], []
    for i in range(args.seeds):
        rng = np.random.default_rng(args.first_seed + i)
        seq = np.zeros(total, np.int32)
        seq[: P + N] = rng.choice(allowed[allowed > 2], P + N)  # bytes, no BOS / EOS / PAD
        seqs.append(seq)
        got.append(program(seq))
        lines.append({"seed": args.first_seed + i})
    controls = tuple(getattr(module, "CONTROLS", ()))
    for lower in (None, *controls):
        module.LOWER = lower
        jax.clear_caches()
        for i, seq in enumerate(seqs):
            ref = module.logits(cfg, gen.params, seq, rows, allowed)
            if lower is None:
                lines[i]["scale"] = np.max(np.abs(ref), axis=1)
            diff = np.max(np.abs(got[i] - ref), axis=1) / lines[i]["scale"]
            lines[i][lower or "program"] = {
                "median": float(np.median(diff)), "p90": float(np.quantile(diff, 0.9)),
                "max": float(np.max(diff)),
                # by the turn of the ring a step is in: a fault of the wrap shows from the second
                "median_by_turn": [float(np.median(diff[a : a + module.RING]))
                                   for a in range(0, N, module.RING)]}
            print(json.dumps({"seed": lines[i]["seed"], lower or "program": lines[i][lower or "program"]}),
                  flush=True)
    module.LOWER = None
    for line in lines:
        del line["scale"]

    def over_seeds(key: str) -> dict:
        read = sorted(line[key]["median"] for line in lines)
        return {"min": read[0], "median": statistics.median(read), "max": read[-1]}

    summary = {"reference": name, "limit": limit, "device": jax.devices()[0].device_kind,
               "shapes": {"slots": B, "positions": S, "prompt_tokens": P, "steps": N,
                          "admit": f"1:{bucket}", "attn_impl": gen.attn_impl, "kv_quant": gen.kv_quant},
               **{key: over_seeds(key) for key in ("program", *controls)}}
    held = summary["program"]["max"] < limit and all(summary[c]["min"] > limit for c in controls)
    summary["held"] = held
    print("SUMMARY", json.dumps(summary), flush=True)
    with open(os.path.join(ROOT, "chiprun_out", f"{name}_logit_hold.json"), "w") as f:
        json.dump({"summary": summary, "seeds": lines}, f)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
