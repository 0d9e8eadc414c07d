#!/usr/bin/env python3
"""The blocked arm of `kernels/attention.py:decode_attend_q8` alone, on the chip.

    chiprun -- python3 scripts/attn_block_sweep.py [--blocks 0 64 128 256] [--tree _clean]

The arm at the shapes the benchmark's generation cells run it at
(`[rows, KV heads, group]` and the cache's length: decode_closed `[32, 8, 4]`
S 2048, solar_decode_closed `[64, 8, 8]` S 1024, olmo_hybrid_decode_closed
`[64, 30, 1]` S 1024, head size 128; granite_decode_closed `[64, 8, 4]` S 1024,
head size 64, which is lfm2_decode_closed's shape too: since PR 55 two heads
abreast in rows of 128 lanes, and in a tree from before that, which `--tree`
may name, a head a row, where the dispatcher answers with the whole-S arm;
packed bf16 scales), each row's position
drawn uniformly between the cell's shortest and longest context so that the
mean is the cell's (`PERF.md` section 5), one row in sixteen parked. For each
block size one jitted function (a function of its own a form: `jax.jit` caches
by function) runs the kernel `--calls` times over the layers of a seeded
cache, as a decode round does; the line gives microseconds a call on the
host's clock around `--reps` such rounds that end in `block_until_ready`, the
tokens the arm streamed and the tokens live, and what the bytes streamed come
to in GB/s. Block 0 is the tree's own rule (`q8_block_tokens`, or the parent's
first-divisor rule with `--tree` at a checkout of the parent: there the other
sizes are skipped, since it has no such argument). `q8_block_tokens` stands on
these readings (`PERF.md` section 6). Refuses to run without a TPU: a CPU time
says nothing about a block size."""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

SHAPES = {  # cell: rows, KV heads, group, cache length, shortest and longest context, head size
    "decode_closed": (32, 8, 4, 2048, 60, 240, 128),
    "solar_decode_closed": (64, 8, 8, 1024, 100, 680, 128),
    "olmo_hybrid_decode_closed": (64, 30, 1, 1024, 100, 680, 128),
    "granite_decode_closed": (64, 8, 4, 1024, 100, 680, 64),
}
LAYERS = 4


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", type=int, nargs="+", default=[0, 64, 128, 256])
    ap.add_argument("--cells", nargs="+", default=list(SHAPES), choices=list(SHAPES))
    ap.add_argument("--calls", type=int, default=144)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    help="the checkout whose llm_mcp_tpu is measured")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_mcp_tpu.kernels import attention as A
    from llm_mcp_tpu.models.quant import pack_scales

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): a block size is a chip reading", file=sys.stderr)
        return 2
    has_block = "block_s" in inspect.signature(A.decode_attend_q8.__wrapped__).parameters
    os.environ["LLM_MCP_TPU_Q8_DECODE"] = "blocked"  # the arm alone, not the cond on the fill

    for cell in args.cells:
        B, Hkv, G, S, lo, hi, HD = SHAPES[cell]
        rng = np.random.default_rng(args.seed)
        key = jax.random.PRNGKey(args.seed)
        # P heads abreast in a row, as the tree's `init_kv_cache` lays this shape
        P = A.kv_heads_abreast(Hkv, HD) if hasattr(A, "kv_heads_abreast") else 1
        rows, W = 2 * Hkv // P, P * HD
        pay = jax.random.randint(key, (LAYERS, B, rows, S, W), -127, 128, jnp.int8)
        s = (jax.random.uniform(key, (LAYERS, B, 2 * Hkv, S)) * 0.02).astype(jnp.bfloat16)
        ck = {"q": jnp.concatenate([pay, pack_scales(s, W)], axis=2), "s": s}
        del pay
        q = jax.random.normal(key, (B, Hkv, G, HD), jnp.bfloat16)
        nk = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, HD), jnp.bfloat16)
        nv = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, HD), jnp.bfloat16)
        w = rng.integers(lo, hi + 1, B)
        w[rng.random(B) < 1 / 16] = S  # parked rows, as a round at 94-97% occupancy has them
        lens = jnp.asarray(w, jnp.int32)
        live = int(np.sum(np.where(w < S, w + 1, 0)))

        ref = A._decode_attend_q8_fallback(q, nk, nv, ck, {}, jnp.int32(1), lens, HD**-0.5, None)
        seated = jnp.asarray(w < S)[:, None, None, None]
        for bs in args.blocks:
            if bs and not has_block:
                continue
            kw = {"block_s": bs} if bs else {}

            def round_fn(q, nk, nv, ck, lens, kw=kw):  # a function of its own a block size
                def call(i, acc):
                    return acc + A.decode_attend_q8(
                        q, nk, nv, ck, {}, i % LAYERS, lens, interpret=False, **kw
                    ).astype(jnp.float32)
                return jax.lax.fori_loop(0, args.calls, call, jnp.zeros(q.shape, jnp.float32))

            fn = jax.jit(round_fn)
            jax.block_until_ready(fn(q, nk, nv, ck, lens))
            one = A.decode_attend_q8(q, nk, nv, ck, {}, jnp.int32(1), lens, interpret=False, **kw)
            err = float(jnp.max(jnp.abs(jnp.where(seated, one.astype(jnp.float32) - ref, 0.0))))
            t0 = time.perf_counter()
            jax.block_until_ready([fn(q, nk, nv, ck, lens) for _ in range(args.reps)])
            us = (time.perf_counter() - t0) * 1e6 / (args.reps * args.calls)
            line = {"cell": cell, "shape": [B, Hkv, G], "S": S, "head": HD, "heads_abreast": P,
                    "device": dev.device_kind, "block": bs or "rule",
                    "max_err_to_f32": round(err, 4), "us_a_call": round(us, 2), "tokens_live": live}
            if has_block:
                eff = bs or A.q8_block_tokens(rows + 1, S, W)
                if W % 128:  # no blocked arm on such rows: the whole-S arm answered
                    eff = S
                streamed = int(np.sum(A.blocked_row_blocks(w, S, eff, xp=np))) * eff
                line.update(block_tokens=eff, tokens_streamed=streamed,
                            live_over_streamed=round(live / streamed, 3),
                            streamed_gb_s=round(streamed * (rows + 1) * W / us / 1e3, 1))
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
